"""The readers of the messenger's control-frame and send counters
(`msgr_ctrl_frames_per_op`, `msgr_sends_per_op`), on hand-built
snapshots and in a tiny traced run of each cell. A third,
`msgr_ctrl_rode_pct` (the share of control frames that left beside a
MESSAGE frame), was retired in PR 41: since PR 37 an ack is a field of
a header and no frame, so nothing rides and the share said nothing;
the counter `ctrl_rode_tx` stays the program's and feeds no reader."""
from __future__ import annotations

import types

import pytest

from tests.benchmarks.test_benchmarks import BENCH, CELLS, _tiny
from tests.benchmarks.test_msgr_rx import ROOT
from benchmarks import harness

NEW = ["msgr_ctrl_frames_per_op", "msgr_sends_per_op"]
SHAPE = {"msgr_ctrl_frames_per_op": ("frames/op", "lower"),
         "msgr_sends_per_op": ("sends/op", "lower")}


def _reader(name):
    return harness._load_module(ROOT, "layer_metrics", name)


def _ctx(before, after, ops=10):
    return types.SimpleNamespace(open={"msgr": before},
                                 close={"msgr": after}, ops=ops)


def _counters(ctrl, rode, sends, **more):
    return dict(ctrl_frames_tx=ctrl, ctrl_rode_tx=rode, tx_sends=sends,
                frames_tx=5, tx_direct_bytes=1, **more)


def entries_stand(bench):
    """PR 30's two stand together after the send path's share (PR 28),
    found by name, so that a later PR's entries do not fail it; the
    retired share has neither an entry nor a reader."""
    names = [m["name"] for m in bench["per_layer"]]
    at = names.index(NEW[0])
    assert names[at:at + 2] == NEW
    assert names[at - 2:at] == ["decode_bitmatrix_roofline",
                                "msgr_tx_direct_pct"]
    assert names.index("msgr_recvs_per_mib") == \
        names.index("msgr_rx_direct_pct") + 1 < at
    assert "msgr_ctrl_rode_pct" not in names
    with pytest.raises(SystemExit):
        _reader("msgr_ctrl_rode_pct")
    for entry in bench["per_layer"][at:at + 2]:
        unit, better = SHAPE[entry["name"]]
        assert entry == {"name": entry["name"], "unit": unit,
                         "better": better, "source": "program_counter",
                         "layer": "msg/messenger", "moves": "ops_s"}
        mod = _reader(entry["name"])
        assert (mod.NAME, mod.UNIT, mod.LAYER, mod.MOVES) == (
            entry["name"], unit, "msg/messenger", "ops_s")


def test_the_two_entries_stand_where_they_stood_and_the_third_is_gone():
    entries_stand(BENCH)


@pytest.mark.parametrize("name", NEW)
@pytest.mark.parametrize("case", ["no_counters", "one_counter_missing",
                                  "only_at_close", "no_ops",
                                  "no_msgr_group"])
def test_reader_finds_nothing_where_there_is_nothing_to_read(name, case):
    """The parent commit sends every frame alone and has no such
    counters: the readers return nothing there and do not raise; a
    window in which no op completed has no per-op figure."""
    old = {"frames_tx": 1, "tx_direct_bytes": 9, "tx_copied_bytes": 1}
    ctx = {
        "no_counters": _ctx(old, old),
        "one_counter_missing": _ctx(
            {k: v for k, v in _counters(0, 0, 0).items()
             if k != "tx_sends"},
            {k: v for k, v in _counters(9, 9, 9).items()
             if k != "tx_sends"}),
        "only_at_close": _ctx(old, _counters(50, 40, 300)),
        "no_ops": _ctx(_counters(0, 0, 0), _counters(50, 40, 300), ops=0),
        "no_msgr_group": types.SimpleNamespace(open={}, close={}, ops=10),
    }[case]
    assert _reader(name).read(ctx) is None


def test_a_window_without_a_control_frame_has_counts_all_the_same():
    """Nor do the two need the retired share's counter: a program that
    drops `ctrl_rode_tx` is read as before."""
    ctx = _ctx(_counters(7, 3, 100), _counters(7, 3, 180), ops=8)
    assert _reader("msgr_ctrl_frames_per_op").read(ctx) == 0.0
    assert _reader("msgr_sends_per_op").read(ctx) == 10.0
    for snap in (ctx.open["msgr"], ctx.close["msgr"]):
        del snap["ctrl_rode_tx"]
    assert _reader("msgr_ctrl_frames_per_op").read(ctx) == 0.0
    assert _reader("msgr_sends_per_op").read(ctx) == 10.0


@pytest.mark.parametrize("ctrl,rode,sends,ops,want", [
    (100, 50, 400, 20, (5.0, 20.0)),
    (9, 9, 27, 3, (3.0, 9.0)),
    (8, 0, 8, 16, (0.5, 0.5)),
    (1000, 925, 2700, 100, (10.0, 27.0)),
])
def test_values_are_the_windows_deltas(ctrl, rode, sends, ops, want):
    before = _counters(700, 600, 5000)
    after = _counters(700 + ctrl, 600 + rode, 5000 + sends)
    got = tuple(_reader(n).read(_ctx(before, after, ops)) for n in NEW)
    assert got == pytest.approx(want)


@pytest.mark.parametrize("cell", CELLS)
def test_tiny_traced_run_reports_the_control_frames(cell, tmp_path):
    """Every cell's ops are answered over connections that owe acks:
    the line of a traced run has both metrics (a window this short may
    frame no control frame at all: an ack is a header's field), and no
    op costs fewer sends than one."""
    done, _cell = _tiny(cell, trace=True, tmp=tmp_path)
    line = done["result"]
    assert line["correct"] is True
    got = {n: line["metrics"][n] for n in NEW}
    assert {n: g["unit"] for n, g in got.items()} == {
        n: SHAPE[n][0] for n in NEW}
    assert got["msgr_ctrl_frames_per_op"]["value"] >= 0
    assert "msgr_ctrl_rode_pct" not in line["metrics"]
    assert got["msgr_sends_per_op"]["value"] >= 1.0
    assert got["msgr_sends_per_op"]["value"] <= \
        line["metrics"]["msgr_frames_per_op"]["value"] \
        + got["msgr_ctrl_frames_per_op"]["value"]
