"""`msgr_acks_carried_pct` (PR 37: the ack a connection owes leaves in
the header of its next MESSAGE frame): the entry, the reader on
hand-built snapshots, and a tiny traced run of each cell. An ack is no
frame any more, so in a window this short no control frame is framed at
all and `msgr_ctrl_rode_pct`, a share of them, has nothing to read. Ten
accepted cases want it on the line and `tests/conftest.py` marks them
`xfail` (`RODE_SILENT`). The runs here hold every assertion of theirs
but that one name: `test_msgr_ctrl.py::test_tiny_traced_run_reports_the_
control_frames` less its "control frames do ride", and each cell's own
tiny run whole, with the name among those its line may lack."""
from __future__ import annotations

import hashlib
import os
import types

import pytest

import tests.benchmarks.test_degraded as degraded_cell
import tests.benchmarks.test_fastread_cell as fastread_cell
import tests.benchmarks.test_scrub_cell as scrub_cell
from tests.benchmarks.test_benchmarks import BENCH, CELLS, ROOT, _tiny
from tests.benchmarks.test_loop_account import SHARES
from tests.benchmarks.test_msgr_ctrl import _ctx, _reader

NAME = "msgr_acks_carried_pct"
ENTRY = {"name": NAME, "unit": "%", "better": "higher",
         "source": "program_counter", "layer": "msg/messenger",
         "moves": "ops_s"}
#: BENCHMARK.json at PR 35 without its closing "\n  ]\n}\n": all that
#: stood before this entry, `per_layer`'s first sixty-three included
PARENT_BYTES = 19295
PARENT_SHA256 = \
    "3910cbafc29e3e37e1d80bee1c7e1de8e622bd6cedf8f8a61d8f9d185cd9db9e"
UNITS = {NAME: "%", "msgr_ctrl_frames_per_op": "frames/op",
         "msgr_sends_per_op": "sends/op", "msgr_frames_per_op": "frames/op"}
#: the share of control frames that rode has none to read in a tiny
#: window; nor has the profiler's trace, which is read on the TPU only
RODE = {"msgr_ctrl_rode_pct"}
FROM_TRACE = {"device_idle_pct", "apply_bitmatrix_batched_roofline"}
#: the cells whose accepted file serves them once, in a `served`
#: fixture that also catches what the harness keeps to itself; and,
#: where it can be called as it stands, the case `RODE_SILENT` marks
FIXTURE = {"rb4m_degraded_seqread": degraded_cell,
           "rb4m_scrub_seqread": scrub_cell,
           "rb4m_fastread_seqread": fastread_cell}
CALLED = {
    "rb4m_degraded_seqread":
        "test_tiny_served_run_is_correct_and_reconstructs",
    "rb4m_fastread_seqread":
        "test_tiny_served_run_is_correct_and_reconstructs"}


def _read(ctx):
    return _reader(NAME).read(ctx)


def _counters(carried, framed, **more):
    return dict(acks_carried_tx=carried, ack_frames_tx=framed,
                ctrl_frames_tx=framed + 3, frames_tx=5, **more)


def test_the_entry_is_the_sixty_fourth_and_what_stood_before_is_the_parents():
    """Byte for byte: the file up to the end of the sixty-third entry
    is the parent's; a prefix check, so a later PR's entries pass it."""
    assert BENCH["per_layer"][63] == ENTRY
    assert [m["name"] for m in BENCH["per_layer"][60:63]] == [
        "decode_device_call_ms.fastread",
        "decode_link_bytes_per_byte.fastread",
        "decode_bitmatrix_roofline.fastread"]
    with open(os.path.join(ROOT, "BENCHMARK.json"), "rb") as f:
        text = f.read()
    assert hashlib.sha256(text[:PARENT_BYTES]).hexdigest() == PARENT_SHA256
    assert text[PARENT_BYTES:].startswith(b',\n    {\n      "name": "%s",'
                                          % NAME.encode())
    mod = _reader(NAME)
    assert (mod.NAME, mod.UNIT, mod.LAYER, mod.MOVES) == (
        NAME, "%", "msg/messenger", "ops_s")
    assert "msg/messenger" in {m["layer"] for m in BENCH["per_layer"][:63]}


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
    """One traced run a cell, kept for both cases below. A cell of
    `FIXTURE` runs through its own file's `served` fixture, window and
    catches included; the other two as their accepted cases run them."""
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


def _write_reports_per_layer_metrics(done, cell):
    """test_benchmarks.py::test_tiny_traced_run_reports_per_layer_
    metrics, its assertions in its order."""
    line = done["result"]
    assert line["correct"] is True
    declared = {r.NAME for r in cell.readers}
    assert set(line["metrics"]) == declared - FROM_TRACE - RODE
    assert line["metrics"]["compiles_in_window"]["value"] == 0
    assert line["metrics"]["store_bytes_per_user_byte"]["value"] == \
        pytest.approx(1.5)          # k=2 m=1
    assert line["metrics"]["link_bytes_per_byte"]["value"] == \
        pytest.approx(1.5, rel=0.1)
    assert 0 < line["metrics"]["loop_busy_pct"]["value"] <= 101


def _seqread_reports_the_loop_and_the_read_path(done, cell):
    """test_loop_account.py::test_tiny_traced_seqread_reports_the_loop_
    and_the_read_path, its assertions in its order."""
    line = done["result"]
    assert line["correct"] is True
    m = {k: v["value"] for k, v in line["metrics"].items()}
    assert set(m) == {r.NAME for r in cell.readers} - FROM_TRACE - RODE
    assert {"ec_read_ms", "loop_msgr_pct", "loop_lag_p95_ms"} <= set(m)
    assert "loop_offload_pct" not in m and "offload_handoff_ms" not in m
    assert m["ec_read_ms"] > 0
    busy = sum(m[n] for n in SHARES if n in m)
    assert 0 < busy <= 100.5
    assert m["loop_msgr_pct"] > 5 and m["loop_osd_pct"] > 5
    assert m["loop_unattributed_pct"] < 10


def _scrub_is_correct_and_finishes_rounds(done, cell, seen):
    """test_scrub_cell.py::test_tiny_served_run_is_correct_and_
    finishes_rounds, its assertions in its order. One is repaired: it
    wants three chunks of every done round but two, which stand for the
    window's edges, and a round of the PG that holds no object scans
    nothing. That PG's turn comes two or three times in 4 s, by when
    set-up ended, and the third broke the case wherever this file
    served the cell (the parent's tree too). Here the rounds that had
    an object are counted, and the edges keep their two."""
    line = done["result"]
    assert line["correct"] is True and line["failed"] == 0
    assert all(value <= limit for _n, value, limit in done["checks"])
    m = {k: v["value"] for k, v in line["metrics"].items()}
    assert set(m) == {r.NAME for r in cell.readers} \
        - scrub_cell.FROM_TRACE - RODE
    assert set(scrub_cell.NEW + scrub_cell.FOUND + scrub_cell.RENAMED) \
        - scrub_cell.FROM_TRACE <= set(m)
    assert m["scrub_errors_found"] == 0
    assert 0 <= m["scrub_pgs_without_round"] <= 4
    assert m["ec_read_ms.scrub"] > 0 and m["loop_offload_pct.scrub"] > 0
    assert 0 < m["offload_lane_busy_pct.scrub"] <= 100
    assert m["compiles_in_window"] == 0
    assert done["info"]["compiles_in_window"] == 0
    assert m["scrub_hashed_mib_s"] > 0 and m["scrub_round_ms"] > 0
    assert 0 <= m["scrub_reserve_failed_pct"] < 100
    assert m["crc_ops_per_batch"] >= 1 and m["crc_device_call_ms"] > 0
    rounds = seen["spans"]["scrub_round"]
    done_rounds = [s["tags"] for s in rounds if s["tags"]["state"] == "done"]
    assert len(done_rounds) >= 4
    legs = {"reserve_us", "grant_wait_us", "scan_us", "digest_us",
            "compare_us"}
    for t in done_rounds:
        assert t["deep"] is True and legs <= set(t)
        assert t["errors"] == t["repaired"] == 0
        assert t["bytes"] == t["objects"] * 32768     # one shard each
    chunks = [s["tags"] for s in seen["spans"]["scrub_chunk"]]
    assert all(t["bytes"] == t["blocks"] * 4096 for t in chunks)
    # every member of a round's PG scans: three chunks a round
    scanned = [t for t in done_rounds if t["objects"]]
    assert len(scanned) >= 4
    assert len(chunks) >= 3 * (len(scanned) - 2)


COPIED = {"rb4m_write": _write_reports_per_layer_metrics,
          "rb4m_seqread": _seqread_reports_the_loop_and_the_read_path,
          "rb4m_scrub_seqread": _scrub_is_correct_and_finishes_rounds}


@pytest.mark.parametrize("cell", CELLS)
def test_the_cells_own_tiny_run_holds_but_for_the_share_that_rode(
        cell, tiny_run, monkeypatch):
    """Each cell's accepted tiny run asserts its line's names against
    its declared readers and then what the cell is for: the rounds, the
    span tags, the bytes a byte, the loop's shares. `xfail` stops those
    cases at the names. Here they run whole: the two `CALLED` bodies
    themselves, with the one name among their `FROM_TRACE`, and the
    three copied above."""
    assert set(CELLS) == set(CALLED) | set(COPIED)
    if cell in COPIED:
        COPIED[cell](*tiny_run(cell))
        return
    mod = FIXTURE[cell]
    monkeypatch.setattr(mod, "FROM_TRACE", mod.FROM_TRACE | RODE)
    getattr(mod, CALLED[cell])(tiny_run(cell))


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
