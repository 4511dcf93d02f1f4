"""`msgr_acks_carried_pct` (PR 37: the ack a connection owes leaves in
the header of its next MESSAGE frame): the entry, the reader on
hand-built snapshots, and a tiny traced run of each cell. An ack is no
frame any more, so in a window this short no control frame may be
framed at all; the share of them that rode, which wanted one, was
retired in PR 41, and the cases that ran each cell's own tiny run here
without that name went back to the files whose bodies they copied."""
from __future__ import annotations

import types

import pytest

import tests.benchmarks.test_degraded as degraded_cell
import tests.benchmarks.test_fastread_cell as fastread_cell
import tests.benchmarks.test_scrub_cell as scrub_cell
from tests.benchmarks.test_benchmarks import BENCH, CELLS, _tiny
from tests.benchmarks.test_msgr_ctrl import _ctx, _reader

NAME = "msgr_acks_carried_pct"
ENTRY = {"name": NAME, "unit": "%", "better": "higher",
         "source": "program_counter", "layer": "msg/messenger",
         "moves": "ops_s"}
UNITS = {NAME: "%", "msgr_ctrl_frames_per_op": "frames/op",
         "msgr_sends_per_op": "sends/op", "msgr_frames_per_op": "frames/op"}
#: the cells whose own file serves them once, in a `served` fixture
#: that also catches what the harness keeps to itself
FIXTURE = {"rb4m_degraded_seqread": degraded_cell,
           "rb4m_scrub_seqread": scrub_cell,
           "rb4m_fastread_seqread": fastread_cell}


def _read(ctx):
    return _reader(NAME).read(ctx)


def _counters(carried, framed, **more):
    return dict(acks_carried_tx=carried, ack_frames_tx=framed,
                ctrl_frames_tx=framed + 3, frames_tx=5, **more)


def entries_stand(bench):
    """PR 37 appended it after the entries of the cell that reads fast;
    PR 41 took that cell's seven renamed readers out from between, and
    four entries before them, so the place is found by name; a later
    PR's entries come after."""
    entries = bench["per_layer"]
    names = [m["name"] for m in entries]
    at = names.index(NAME)
    assert entries[at] == ENTRY
    assert names[at - 6:at] == fastread_cell.NEW
    assert names[at + 1] == "loop_cpu_ms_per_op"
    mod = _reader(NAME)
    assert (mod.NAME, mod.UNIT, mod.LAYER, mod.MOVES) == (
        NAME, "%", "msg/messenger", "ops_s")
    assert "msg/messenger" in {m["layer"] for m in entries[:at]}


def test_the_entry_stands_after_the_fastread_cells_six_and_is_the_parents():
    entries_stand(BENCH)


@pytest.mark.parametrize("case", ["no_counters", "one_counter_missing",
                                  "only_at_close", "no_ops", "zero_sum",
                                  "no_msgr_group"])
def test_reader_finds_nothing_where_there_is_nothing_to_read(case):
    """The parent frames every ack and counts neither kind: the reader
    returns nothing there and does not raise; nor has a window without
    an op, or without an ack, a share."""
    old = {"frames_tx": 1, "ctrl_frames_tx": 9, "ctrl_rode_tx": 8}
    ctx = {
        "no_counters": _ctx(old, old),
        "one_counter_missing": _ctx(
            {k: v for k, v in _counters(0, 0).items()
             if k != "ack_frames_tx"},
            {k: v for k, v in _counters(9, 9).items()
             if k != "ack_frames_tx"}),
        "only_at_close": _ctx(old, _counters(50, 4)),
        "no_ops": _ctx(_counters(0, 0), _counters(50, 4), ops=0),
        "zero_sum": _ctx(_counters(50, 4), _counters(50, 4, tx_sends=90)),
        "no_msgr_group": types.SimpleNamespace(open={}, close={}, ops=10),
    }[case]
    assert _read(ctx) is None


@pytest.mark.parametrize("carried,framed,want", [
    (90, 10, 90.0), (0, 8, 0.0), (7, 0, 100.0), (10260, 540, 95.0)])
def test_the_value_is_the_windows_deltas(carried, framed, want):
    before = _counters(700, 600)
    after = _counters(700 + carried, 600 + framed)
    assert _read(_ctx(before, after, ops=3)) == pytest.approx(want)


# -- every cell served, tiny, on the CPU backend ------------------------------

@pytest.fixture(scope="module")
def tiny_run(tmp_path_factory):
    """One traced run a cell. A cell of `FIXTURE` runs through its own
    file's `served` fixture, window and catches included; the other two
    as their accepted cases run them."""
    runs: dict = {}

    def run(cell):
        if cell not in runs:
            if cell in FIXTURE:
                runs[cell] = FIXTURE[cell].served.__wrapped__(
                    tmp_path_factory)
            else:
                runs[cell] = _tiny(cell, trace=True,
                                   tmp=tmp_path_factory.mktemp(cell))
        return runs[cell]
    return run


@pytest.mark.parametrize("cell", CELLS)
def test_tiny_traced_run_reports_the_acks(cell, tiny_run):
    """Every cell's ops are answered over connections that owe acks:
    a traced run is correct inside every limit, compiles nothing, has
    the four metrics with their units, most acks left in a header, and
    no op costs fewer sends than one nor more than it has frames."""
    done = tiny_run(cell)[0]
    line = done["result"]
    assert line["correct"] is True and line["failed"] == 0
    assert all(value <= limit for _n, value, limit in done["checks"])
    assert done["info"]["compiles_in_window"] == 0
    assert "msgr_ctrl_rode_pct" not in line["metrics"]
    got = {n: line["metrics"][n] for n in UNITS}
    assert {n: g["unit"] for n, g in got.items()} == UNITS
    assert 50.0 < got[NAME]["value"] <= 100.0
    assert got["msgr_ctrl_frames_per_op"]["value"] >= 0.0
    assert 1.0 <= got["msgr_sends_per_op"]["value"] <= \
        got["msgr_frames_per_op"]["value"] \
        + got["msgr_ctrl_frames_per_op"]["value"]
