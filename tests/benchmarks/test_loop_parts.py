"""The sixteen `*_ms_per_op` readers (PR 39: the loop account charges a
part of a label, `loop_slice` carries the parts): the entries, the shared
helper on hand-made slices, and a tiny traced run of each cell, whose
parts add up to their labels and whose labels add up to the loop's CPU
an op."""
from __future__ import annotations

import types

import pytest

from benchmarks import harness
from benchmarks.layer_metrics import loop_parts, loop_share
from tests.benchmarks.test_benchmarks import BENCH, CELLS, ROOT, _tiny

MSGR = ("rx_sock", "rx_alloc", "rx_frame", "codec", "tx_frame", "tx_sock",
        "dispatch", "handler", "other")
OSD = ("pg", "ec", "subop", "queue", "other")
WHOLE = "loop_cpu_ms_per_op"
SCRUB = "osd_scrub_ms_per_op"
#: name -> (layer, the part it reads; None: every label but `idle`)
READERS = {WHOLE: ("event loop (all daemons)", None)}
READERS.update({f"msgr_{p}_ms_per_op": ("msg/messenger", f"msgr.{p}")
                for p in MSGR})
READERS.update({f"osd_{p}_ms_per_op": ("osd/pg+osd/ec_backend", f"osd.{p}")
                for p in OSD})
READERS[SCRUB] = ("osd/scrub", "osd.scrub")
PARTS = [f"msgr.{p}" for p in MSGR] + [f"osd.{p}" for p in OSD + ("scrub",)]


def _reader(name):
    return harness._load_module(ROOT, "layer_metrics", name)


def _slice(parts=None, **us):
    tags = {k + "_us": float(us.get(k, 0.0)) for k in loop_share.LABELS}
    if parts is not None:
        tags["parts"] = dict.fromkeys(PARTS, 0.0) | parts
    return {"name": "loop_slice", "duration_us": sum(us.values()),
            "tags": tags}


def _ctx(ops, *slices):
    return types.SimpleNamespace(ops=ops, spans={"loop_slice": list(slices)})


# -- the entries ---------------------------------------------------------------

def entries_stand(bench):
    """PR 39 appended them after `msgr_acks_carried_pct`; PR 41 took
    eleven entries out before them, so the place is found by name, and
    a later PR's entries come after."""
    entries = bench["per_layer"]
    names = [m["name"] for m in entries]
    at = names.index(WHOLE)
    added = entries[at:at + 16]
    assert [m["name"] for m in added] == list(READERS)
    for m in added:
        layer, _part = READERS[m["name"]]
        want = {"name": m["name"], "unit": "ms/op", "better": "lower",
                "source": "program_span", "layer": layer, "moves": "ops_s"}
        if m["name"] == SCRUB:
            assert "rb4m_scrub_seqread" in m["workloads"]
            want["workloads"] = m["workloads"]
        assert m == want
    assert names[at - 1] == "msgr_acks_carried_pct"
    assert not any(n.endswith("_ms_per_op") for n in names[:at])
    assert {layer for layer, _ in READERS.values()} <= \
        {m["layer"] for m in entries[:at]}


def test_the_sixteen_entries_stand_in_their_order_after_the_acks_share():
    entries_stand(BENCH)


@pytest.mark.parametrize("name", READERS)
def test_reader_is_its_entry_and_one_call_of_the_helper(name):
    mod = _reader(name)
    layer, part = READERS[name]
    assert (mod.NAME, mod.UNIT, mod.LAYER, mod.MOVES) == (
        name, "ms/op", layer, "ops_s")
    mine = part or "osd.pg"
    a = _slice({"msgr.codec": 100.0, "osd.queue": 50.0} | {mine: 700.0},
               msgr=800.0, osd=700.0, idle=500.0)
    b = _slice({mine: 300.0}, msgr=300.0, osd=300.0, gc=400.0)
    got = mod.read(_ctx(4, a, b))
    if part is None:            # all but `idle`, whatever the parts say
        assert got == pytest.approx((800 + 700 + 300 + 300 + 400) / 4 / 1e3)
    else:                       # its own part, and no other's
        assert got == pytest.approx(1000.0 / 4 / 1e3)


# -- the helper ----------------------------------------------------------------

def test_a_part_is_its_microseconds_over_two_slices_by_the_ops():
    a = _slice({"msgr.rx_sock": 1500.0, "osd.ec": 10.0}, msgr=1500.0)
    b = _slice({"msgr.rx_sock": 500.0}, msgr=500.0, idle=100.0)
    ctx = _ctx(8, a, b)
    assert loop_parts.ms_per_op(ctx, "msgr.rx_sock") == pytest.approx(0.25)
    assert loop_parts.ms_per_op(ctx, "osd.ec") == pytest.approx(0.00125)
    assert loop_parts.ms_per_op(ctx, "osd.pg") == 0.0   # read, and nothing
    assert loop_parts.busy_ms_per_op(ctx) == pytest.approx(0.25)


@pytest.mark.parametrize("name", READERS)
def test_reader_finds_nothing_on_a_program_without_the_parts(name):
    """The parent's slices carry no `parts`: None, never 0 for absent;
    and so where no op completed, or the program closes no slice."""
    mod = _reader(name)
    flat = _slice(msgr=900.0, osd=100.0)
    assert mod.read(_ctx(5, flat)) is None
    assert mod.read(_ctx(5)) is None
    assert mod.read(types.SimpleNamespace(ops=5, spans={})) is None
    assert mod.read(_ctx(0, _slice({"msgr.codec": 1.0}, msgr=1.0))) is None
    # a slice of an older account among newer ones counts for nothing
    mixed = _ctx(2, flat, _slice({"msgr.codec": 4000.0}, msgr=4000.0))
    if READERS[name][1] == "msgr.codec":
        assert mod.read(mixed) == pytest.approx(2.0)


def test_a_part_the_account_does_not_know_reads_nothing():
    ctx = _ctx(2, _slice({"msgr.codec": 4.0}, msgr=4.0))
    assert loop_parts.ms_per_op(ctx, "msgr.no_such_part") is None


# -- a tiny traced run of each cell ----------------------------------------------

@pytest.fixture(scope="module", params=CELLS)
def traced(request, tmp_path_factory):
    """One traced run of the cell through `_tiny`, with what the readers
    were given (`harness.Ctx`) caught on the way."""
    seen: dict = {}
    real = harness.Ctx

    def ctx(**kw):
        seen["ctx"] = real(**kw)
        return seen["ctx"]
    harness.Ctx = ctx
    try:
        done, cell = _tiny(request.param, trace=True, seconds=1.5,
                           tmp=tmp_path_factory.mktemp("traced"))
    finally:
        harness.Ctx = real
    return request.param, done, cell, seen["ctx"]


def test_tiny_traced_run_reports_the_family(traced):
    name, done, cell, _ctx_seen = traced
    line = done["result"]
    assert line["correct"] is True and line["failed"] == 0
    m = {k: v["value"] for k, v in line["metrics"].items()}
    want = set(READERS) - ({SCRUB} if name != "rb4m_scrub_seqread" else set())
    assert want <= set(m) and (SCRUB in m) == (SCRUB in want)
    assert want <= {r.NAME for r in cell.readers}
    assert all(line["metrics"][n]["unit"] == "ms/op" for n in want)
    assert all(m[n] >= 0 for n in want)
    for part in ("rx_sock", "rx_frame", "codec", "tx_frame", "tx_sock",
                 "dispatch"):
        assert m[f"msgr_{part}_ms_per_op"] > 0
    for part in ("pg", "ec", "subop", "queue"):
        assert m[f"osd_{part}_ms_per_op"] > 0
    if SCRUB in want:
        assert m[SCRUB] > 0


def test_tiny_traced_runs_parts_add_up(traced):
    """The whole is every label but `idle` over the ops; the nine parts
    of `msgr` are its label, the parts of `osd` theirs, within 1%."""
    name, done, _cell, ctx = traced
    m = {k: v["value"] for k, v in done["result"]["metrics"].items()}
    by = loop_share.totals(ctx)
    assert ctx.ops > 0
    assert m[WHOLE] == pytest.approx(
        (sum(by.values()) - by["idle"]) / ctx.ops / 1000.0)
    assert sum(m[f"msgr_{p}_ms_per_op"] for p in MSGR) == pytest.approx(
        by["msgr"] / ctx.ops / 1000.0, rel=0.01)
    osd = sum(m[f"osd_{p}_ms_per_op"] for p in OSD)
    # the two parts that only their own cell has a reader for: what the
    # reader gives there, the span's elsewhere (0 where no such work)
    for part, reader in (("osd.scrub", SCRUB),
                         ("osd.recovery", "osd_recovery_ms_per_op")):
        osd += m[reader] if reader in m else loop_parts.ms_per_op(ctx, part)
    assert osd == pytest.approx(by["osd"] / ctx.ops / 1000.0, rel=0.01)
    # what the accepted shares read is what the parts sum to
    assert m["loop_msgr_pct"] == pytest.approx(
        100.0 * by["msgr"] / sum(by.values()))
    assert m["loop_osd_pct"] == pytest.approx(
        100.0 * by["osd"] / sum(by.values()))
