"""A process that runs an OSD spaces out the collector's full rounds
(`osd/daemon._space_out_full_collections`): what the OSD stores lives
in its heap, and a full collection walks all of it."""
from __future__ import annotations

import gc

import pytest

from ceph_tpu.osd import daemon
from ceph_tpu.tools.cluster_boot import ephemeral_cluster

from tests.test_cluster import run


@pytest.fixture
def thresholds():
    kept = gc.get_threshold()
    yield kept
    gc.set_threshold(*kept)


def test_a_started_osd_leaves_the_young_rounds_and_spaces_the_full_ones(
        thresholds):
    gc.set_threshold(700, 10, 10)       # CPython's own

    async def body():
        async with ephemeral_cluster(1, prefix="gc-"):
            return gc.get_threshold()
    assert run(body(), timeout=60) == (700, 10, daemon.FULL_GC_EVERY)
    assert daemon.FULL_GC_EVERY == 100


@pytest.mark.parametrize("old,want", [(10, 100), (100, 100), (500, 500)])
def test_the_rule_never_lowers_what_the_process_has(thresholds, old, want):
    gc.set_threshold(1234, 7, old)
    daemon._space_out_full_collections()
    daemon._space_out_full_collections()
    assert gc.get_threshold() == (1234, 7, want)
