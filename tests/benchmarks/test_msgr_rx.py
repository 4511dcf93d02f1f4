"""The readers of the messenger endpoint's receive counters, on hand-built
snapshots and in a tiny traced run of each cell."""
from __future__ import annotations

import os
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import harness  # noqa: E402
from tests.benchmarks.test_benchmarks import BENCH, CELLS, _tiny  # noqa: E402

NEW = ["msgr_rx_direct_pct", "msgr_recvs_per_mib"]
MIB = 2 ** 20


def _reader(name):
    return harness._load_module(ROOT, "layer_metrics", name)


def _ctx(before, after):
    return types.SimpleNamespace(open={"msgr": before},
                                 close={"msgr": after})


def _counters(direct, spill, recvs, **more):
    return dict(rx_direct_bytes=direct, rx_spill_bytes=spill,
                rx_recvs=recvs, frames_tx=5, **more)


#: the per-layer metrics accepted before PR 25, in BENCHMARK.json's
#: order, less `offload_lane_busy_pct`, which PR 44 retired
ACCEPTED = [
    "loop_busy_pct", "msgr_frames_per_op", "queue_wait_pct", "ec_encode_ms",
    "offload_ops_per_batch", "link_bytes_per_byte",
    "apply_bitmatrix_batched_roofline", "device_idle_pct",
    "compiles_in_window", "store_commit_ms", "store_bytes_per_user_byte",
    "loop_msgr_pct", "loop_client_pct", "loop_osd_pct", "loop_offload_pct",
    "loop_store_pct", "loop_harness_pct", "loop_gc_pct",
    "loop_unattributed_pct", "loop_lag_p95_ms", "offload_handoff_ms",
    "offload_device_call_ms", "ec_read_ms"]


def entries_stand(bench):
    """A prefix check of the first cells' order, so that a later PR's
    entries do not fail it."""
    names = [m["name"] for m in bench["per_layer"]]
    assert names[:len(ACCEPTED)] == ACCEPTED
    assert names[len(ACCEPTED):len(ACCEPTED) + 2] == NEW
    by = {m["name"]: m for m in bench["per_layer"]}
    for n in NEW:
        assert "workloads" not in by[n]
        assert by[n]["source"] == "program_counter"
        assert by[n]["layer"] == "msg/messenger"
    assert (by[NEW[0]]["better"], by[NEW[0]]["moves"]) == ("higher", "ops_s")
    assert (by[NEW[1]]["better"], by[NEW[1]]["moves"]) == \
        ("lower", "op_p50_ms")


def test_the_two_entries_are_appended_and_nothing_before_them_moved():
    entries_stand(BENCH)


@pytest.mark.parametrize("name", NEW)
@pytest.mark.parametrize("case", ["no_counters", "one_counter_missing",
                                  "only_at_close", "nothing_received",
                                  "no_msgr_group"])
def test_reader_finds_nothing_where_there_is_nothing_to_read(name, case):
    """The parent commit runs on asyncio's streams and has no such
    counters; a window in which nothing was received has no share."""
    mod = _reader(name)
    old = {"frames_tx": 1, "frames_rx": 1}
    ctx = {
        "no_counters": _ctx(old, old),
        "one_counter_missing": _ctx(
            {k: v for k, v in _counters(0, 0, 0).items()
             if k != "rx_spill_bytes"},
            {k: v for k, v in _counters(9, 9, 9).items()
             if k != "rx_spill_bytes"}),
        "only_at_close": _ctx(old, _counters(5 * MIB, MIB, 7)),
        "nothing_received": _ctx(_counters(MIB, 10, 3),
                                 _counters(MIB, 10, 3)),
        "no_msgr_group": types.SimpleNamespace(open={}, close={}),
    }[case]
    assert mod.read(ctx) is None


@pytest.mark.parametrize("direct,spill,recvs,pct,per_mib", [
    (99 * MIB, MIB, 150, 99.0, 1.5),
    (0, 2 * MIB, 4096, 0.0, 2048.0),
    (8 * MIB, 0, 8, 100.0, 1.0),
    (3 * MIB // 2, MIB // 2, 1, 75.0, 0.5),
])
def test_values_are_deltas_over_the_window(direct, spill, recvs, pct,
                                           per_mib):
    before = _counters(7 * MIB, 3 * MIB, 1000)
    after = _counters(7 * MIB + direct, 3 * MIB + spill, 1000 + recvs)
    ctx = _ctx(before, after)
    assert _reader(NEW[0]).read(ctx) == pytest.approx(pct)
    assert _reader(NEW[1]).read(ctx) == pytest.approx(per_mib)


@pytest.mark.parametrize("cell", CELLS)
def test_tiny_traced_run_reports_the_receive_path(cell, tmp_path):
    """Both cells move their payloads through the endpoint: the line of
    a traced run has both metrics, and small objects (the tiny size)
    read differently from the chip's 4 MiB: mostly through the spill."""
    done, _cell = _tiny(cell, trace=True, tmp=tmp_path)
    line = done["result"]
    assert line["correct"] is True
    m = {k: v["value"] for k, v in line["metrics"].items()}
    assert 0.0 <= m["msgr_rx_direct_pct"] <= 100.0
    assert m["msgr_recvs_per_mib"] > 0.0
    assert line["metrics"]["msgr_recvs_per_mib"]["unit"] == "recvs/MiB"
