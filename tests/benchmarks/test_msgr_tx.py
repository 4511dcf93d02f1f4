"""The reader of the messenger's send counters (`msgr_tx_direct_pct`), on
hand-built snapshots and in a tiny traced run of each cell."""
from __future__ import annotations

import types

import pytest

from tests.benchmarks.test_benchmarks import BENCH, CELLS, _tiny
from tests.benchmarks.test_msgr_rx import ROOT, _ctx
from benchmarks import harness

NAME = "msgr_tx_direct_pct"
MIB = 2 ** 20


def _reader():
    return harness._load_module(ROOT, "layer_metrics", NAME)


def _counters(direct, copied, **more):
    return dict(tx_direct_bytes=direct, tx_copied_bytes=copied,
                frames_tx=5, rx_direct_bytes=1, **more)


def entries_stand(bench):
    """PR 28 appended it after the degraded cell's seven, found by
    name, so that a later PR's entries do not fail it."""
    names = [m["name"] for m in bench["per_layer"]]
    at = names.index(NAME)
    assert names[at - 1] == "decode_bitmatrix_roofline"
    assert names.index("msgr_recvs_per_mib") == \
        names.index("msgr_rx_direct_pct") + 1 < at
    entry = bench["per_layer"][at]
    assert entry == {"name": NAME, "unit": "%", "better": "higher",
                     "source": "program_counter", "layer": "msg/messenger",
                     "moves": "ops_s"}
    mod = _reader()
    assert (mod.NAME, mod.UNIT, mod.LAYER, mod.MOVES) == (
        NAME, "%", "msg/messenger", "ops_s")


def test_the_entry_is_appended_and_nothing_before_it_moved():
    entries_stand(BENCH)


@pytest.mark.parametrize("case", ["no_counters", "one_counter_missing",
                                  "only_at_close", "nothing_sent",
                                  "no_msgr_group"])
def test_reader_finds_nothing_where_there_is_nothing_to_read(case):
    """The parent commit packs every frame and has no such counters: the
    reader returns nothing there and does not raise; a window in which
    nothing was sent has no share."""
    old = {"frames_tx": 1, "rx_direct_bytes": 9, "rx_spill_bytes": 1}
    ctx = {
        "no_counters": _ctx(old, old),
        "one_counter_missing": _ctx(
            {k: v for k, v in _counters(0, 0).items()
             if k != "tx_copied_bytes"},
            {k: v for k, v in _counters(9, 9).items()
             if k != "tx_copied_bytes"}),
        "only_at_close": _ctx(old, _counters(5 * MIB, MIB)),
        "nothing_sent": _ctx(_counters(MIB, 10), _counters(MIB, 10)),
        "no_msgr_group": types.SimpleNamespace(open={}, close={}),
    }[case]
    assert _reader().read(ctx) is None


@pytest.mark.parametrize("direct,copied,pct", [
    (99 * MIB, MIB, 99.0),
    (0, 2 * MIB, 0.0),
    (8 * MIB, 0, 100.0),
    (3 * MIB // 2, MIB // 2, 75.0),
    (9998, 2, 99.98),
])
def test_value_is_a_share_of_the_windows_deltas(direct, copied, pct):
    before = _counters(7 * MIB, 3 * MIB)
    after = _counters(7 * MIB + direct, 3 * MIB + copied)
    assert _reader().read(_ctx(before, after)) == pytest.approx(pct)


@pytest.mark.parametrize("cell", CELLS)
def test_tiny_traced_run_reports_the_send_path(cell, tmp_path):
    """Every cell frames through the write loop: the line of a traced
    run has the metric. At the tiny size (64 KiB objects in 32 KiB
    shards) only a read's reply to the client reaches the line, so the
    share is far from the chip's 99%."""
    done, _cell = _tiny(cell, trace=True, tmp=tmp_path)
    line = done["result"]
    assert line["correct"] is True
    got = line["metrics"][NAME]
    assert got["unit"] == "%" and 0.0 <= got["value"] <= 100.0
    if "read" in cell:
        assert got["value"] > 20.0
