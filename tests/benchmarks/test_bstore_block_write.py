"""`bstore_block_write_ms` (PR 46: BlueStore's prepare stages a shard's
extents and the store's commit thread writes them, ahead of its group's
sync): the entry, the reader on hand-made `bstore_kv_sync` spans, and
which cells load it."""
from __future__ import annotations

import types

import pytest

from benchmarks import harness
from tests.benchmarks.test_benchmarks import BENCH, CELLS, ROOT

NAME = "bstore_block_write_ms"
CELL = "rb4m_bluestore_write"
ENTRY = {"name": NAME, "unit": "ms", "better": "lower",
         "source": "program_span", "layer": "objectstore",
         "moves": "op_p50_ms", "workloads": [CELL]}


def _read(groups):
    mod = harness._load_module(ROOT, "layer_metrics", NAME)
    return mod.read(types.SimpleNamespace(
        spans={"bstore_kv_sync": groups} if groups is not None else {}))


def _group(write_us=None, writes=0, length_us=3000.0, **more):
    """A group commit's span as `BlueStore._commit_group` records it;
    the parent's has neither of the two tags."""
    tags = {"group": 1, "txcs": 2, "block_synced": int(bool(writes)),
            "block_sync_us": length_us / 2, "kv_submit_us": length_us / 2,
            "kv_fsyncs": 1, "block_bytes": writes * 524288,
            "kv_bytes": 9000, "freelist_bytes": 700, **more}
    if write_us is not None:
        tags.update(block_write_us=write_us, block_writes=writes)
    return {"name": "bstore_kv_sync", "duration_us": length_us,
            "tags": tags}


def entries_stand(bench, root=ROOT):
    """PR 46 appended the one entry after the seven of PR 45's cell; it
    is found by name, and a later PR's come after it."""
    entries = bench["per_layer"]
    names = [m["name"] for m in entries]
    at = names.index(NAME)
    assert entries[at] == {**ENTRY, "workloads": entries[at]["workloads"]}
    assert CELL in entries[at]["workloads"]
    assert names[at - 1] == "bstore_acks_before_sync"
    mod = harness._load_module(root, "layer_metrics", NAME)
    assert (mod.NAME, mod.UNIT, mod.LAYER, mod.MOVES) == (
        NAME, "ms", "objectstore", "op_p50_ms")


def test_the_entry_stands_after_the_bluestore_cells_seven():
    entries_stand(BENCH)
    assert BENCH["per_layer"][
        [m["name"] for m in BENCH["per_layer"]].index(NAME)] == ENTRY


@pytest.mark.parametrize("case,groups,want", [
    # the median of the groups that wrote, and of those alone
    ("wrote", [_group(1500.0, 1), _group(2500.0, 2), _group(900.0, 1)], 1.5),
    ("wrote_among_idle", [_group(0.4, 0), _group(2000.0, 1), _group(0.3, 0),
                          _group(0.5, 0), _group(4000.0, 3)], 3.0),
    ("one", [_group(1234.0, 11)], 1.234),
    # the tag is there and every object fit its onode: a reading, 0
    ("none_wrote", [_group(0.6, 0), _group(0.2, 0)], 0.0),
    # a program that writes while it prepares: nothing to read
    ("no_tag", [_group(), _group()], None),
    ("not_a_group", [{"name": "bstore_kv_sync", "duration_us": 1.0,
                      "tags": {"block_write_us": 5.0}}], None),
    ("no_groups", [], None),
    ("no_such_span", None, None),
    # spans of both programs (cannot happen in one run): the tagged ones
    ("mixed", [_group(), _group(700.0, 1)], 0.7),
])
def test_reader_on_hand_made_spans(case, groups, want):
    got = _read(groups)
    assert got == (pytest.approx(want) if want is not None else None)
    if want == 0.0:
        assert got is not None and isinstance(got, float)


def test_the_write_is_a_part_of_the_block_sync_leg_and_of_no_other():
    """What the accepted readers sum stays whole: `block_write_us` is
    not among `bstore_commit_wait_ms`'s legs, and `bstore_sync_ms`
    reads the span's length, which holds it."""
    wait = harness._load_module(ROOT, "layer_metrics",
                                "bstore_commit_wait_ms")
    assert wait.LEGS == ("queued_us", "block_sync_us", "kv_submit_us",
                         "deliver_us")
    sync = harness._load_module(ROOT, "layer_metrics", "bstore_sync_ms")
    ctx = types.SimpleNamespace(
        spans={"bstore_kv_sync": [_group(1000.0, 1, length_us=5000.0)]})
    assert sync.read(ctx) == pytest.approx(5.0)


@pytest.mark.parametrize("cell", CELLS)
def test_only_the_bluestore_cell_loads_it(cell):
    loaded = [r.NAME for r in harness.load_cell(cell).readers]
    assert (NAME in loaded) == (cell == CELL)
    if cell == CELL:
        assert loaded.index(NAME) > loaded.index("bstore_acks_before_sync")
