"""The four readers of the account's own cost (PR 55: a `loop_slice`
span carries `instr`, the instruments' microseconds by kind and by the
part that had been charged them, and the spans and sections closed; the
`callbacks` tag is PR 24's): the entries, the readers on hand-made
slices, and a tiny traced run of three cells, where the account's
"of which" stays inside the whole it is a part of."""
from __future__ import annotations

import types

import pytest

from benchmarks import harness
from benchmarks.layer_metrics import loop_share
from tests.benchmarks.test_benchmarks import BENCH, ROOT, _tiny

NEW = {"instr_ms_per_op": "ms/op", "instr_in_frame_ms_per_op": "ms/op",
       "spans_per_op": "spans/op", "loop_callbacks_per_op": "callbacks/op"}
LAYER = "event loop (all daemons)"
FRAME = ("msgr.rx_frame", "msgr.tx_frame", "msgr.dispatch", "msgr.handler")


def _reader(name):
    return harness._load_module(ROOT, "layer_metrics", name)


def _slice(instr=None, callbacks=None, **us):
    tags = {k + "_us": float(us.get(k, 0.0)) for k in loop_share.LABELS}
    if instr is not None:
        tags["instr"] = instr
    if callbacks is not None:
        tags["callbacks"] = callbacks
    return {"name": "loop_slice", "duration_us": sum(us.values()),
            "tags": tags}


def _instr(spans=0, sections=0, in_part=None, **by_kind):
    kinds = dict.fromkeys(("span", "section", "hook", "roll"), 0.0) | by_kind
    return {"by_kind": kinds, "in_part": in_part or {}, "spans": spans,
            "sections": sections}


def _ctx(ops, *slices):
    return types.SimpleNamespace(ops=ops, spans={"loop_slice": list(slices)})


def entries_stand(bench):
    """Found by name, so that a later PR's entries do not fail it: the
    four stand together, in ISSUE 55's order, after every entry that
    stood before them, and every cell reports them."""
    names = [m["name"] for m in bench["per_layer"]]
    by = {m["name"]: m for m in bench["per_layer"]}
    at = names.index("instr_ms_per_op")
    assert names[at:at + 4] == list(NEW)
    assert at > names.index("msgr_tx_worker_busy_pct")
    assert at > names.index("loop_cpu_ms_per_op")
    for name, unit in NEW.items():
        assert by[name] == {"name": name, "unit": unit, "better": "lower",
                            "source": "program_span", "layer": LAYER,
                            "moves": "ops_s"}


def test_the_four_entries_are_appended_after_the_send_workers():
    entries_stand(BENCH)


@pytest.mark.parametrize("name", NEW)
def test_reader_is_its_entry(name):
    mod = _reader(name)
    assert (mod.NAME, mod.UNIT, mod.LAYER, mod.MOVES) == \
        (name, NEW[name], LAYER, "ops_s")


@pytest.mark.parametrize("name", NEW)
@pytest.mark.parametrize("case", ["no_slices", "no_span_group", "no_op",
                                  "parent"])
def test_reader_finds_nothing_where_there_is_nothing_to_read(name, case):
    """A parent whose slices carry no `instr`, a window in which no op
    completed, a program that closes no slice: None, never 0 for absent,
    and nothing raises. The `callbacks` tag is older than `instr`: a
    parent has it, and its reader reads it there."""
    full = _slice(_instr(3, 2, {"msgr.tx_frame": 5.0}, span=5.0), 7,
                  msgr=100.0)
    ctx = {"no_slices": _ctx(5),
           "no_span_group": types.SimpleNamespace(ops=5, spans={}),
           "no_op": _ctx(0, full),
           "parent": _ctx(4, _slice(None, 12, msgr=900.0, idle=100.0))}[case]
    if (name, case) == ("loop_callbacks_per_op", "parent"):
        assert _reader(name).read(ctx) == pytest.approx(3.0)
    else:
        assert _reader(name).read(ctx) is None


def test_readers_sum_the_windows_slices_over_the_ops():
    a = _slice(_instr(40, 100, {"msgr.rx_frame": 300.0, "msgr.tx_frame": 200.0,
                                "msgr.codec": 150.0, "osd.ec": 50.0,
                                "background": 10.0},
                      span=400.0, section=100.0, hook=200.0, roll=10.0),
               90, msgr=5000.0, osd=400.0, idle=100.0)
    b = _slice(_instr(8, 0, {"msgr.dispatch": 60.0, "msgr.handler": 30.0,
                             "client": 10.0}, span=80.0, hook=20.0),
               30, msgr=900.0, client=50.0)
    older = _slice(None, 40, msgr=700.0)    # a slice without `instr`
    ctx = _ctx(8, a, b, older)
    assert _reader("instr_ms_per_op").read(ctx) == \
        pytest.approx(810.0 / 8 / 1e3)
    assert _reader("instr_in_frame_ms_per_op").read(ctx) == \
        pytest.approx(590.0 / 8 / 1e3)
    assert _reader("spans_per_op").read(ctx) == pytest.approx(6.0)
    assert _reader("loop_callbacks_per_op").read(ctx) == pytest.approx(20.0)


def test_the_frame_reader_reads_the_four_parts_of_a_frame_and_no_other():
    mod = _reader("instr_in_frame_ms_per_op")
    assert mod.PARTS == FRAME
    for part in FRAME:
        ctx = _ctx(2, _slice(_instr(in_part={part: 4000.0, "msgr.codec": 9.0,
                                             "osd.subop": 9.0}, span=4018.0)))
        assert mod.read(ctx) == pytest.approx(2.0)
    none = _ctx(2, _slice(_instr(in_part={"msgr.rx_sock": 7.0}, hook=7.0)))
    assert mod.read(none) == 0.0        # read, and nothing of it in a frame


@pytest.fixture(scope="module", params=["rb4m_write", "rb4m_seqread",
                                        "rb64k_write"])
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
        done, _cell = _tiny(request.param, trace=True, seconds=1.0,
                            tmp=tmp_path_factory.mktemp("instr"))
    finally:
        harness.Ctx = real
    return done["result"], seen["ctx"]


def test_a_traced_run_reports_the_four_beside_the_whole(traced):
    line, _ctx = traced
    assert line["correct"] is True
    m = {k: v["value"] for k, v in line["metrics"].items()}
    for name, unit in NEW.items():
        assert line["metrics"][name]["unit"] == unit
    assert 0.0 < m["instr_ms_per_op"] < m["loop_cpu_ms_per_op"]
    assert 0.0 <= m["instr_in_frame_ms_per_op"] <= m["instr_ms_per_op"]
    frame = sum(m[f"msgr_{p}_ms_per_op"] for p in
                ("rx_frame", "tx_frame", "dispatch", "handler"))
    assert m["instr_in_frame_ms_per_op"] <= frame   # a part of those four
    assert m["spans_per_op"] >= 5.0     # rados_op .. store_commit, at least
    assert m["loop_callbacks_per_op"] >= 10.0


def test_a_traced_runs_slices_book_the_observer_by_kind_and_part_alike(
        traced):
    _line, ctx = traced
    slices = [s["tags"] for s in ctx.spans["loop_slice"]]
    assert len(slices) >= 5 and all("instr" in t for t in slices)
    for t in slices:
        instr = t["instr"]
        assert sum(instr["by_kind"].values()) == pytest.approx(
            sum(instr["in_part"].values()), rel=1e-6, abs=1e-3)
        assert set(instr["in_part"]) <= set(t["parts"]) | {
            k[:-3] for k in t if k.endswith("_us")}
        # the annotation's keys are the slice's `*_us`: none of them new
        assert {k for k in t if k.endswith("_us")} == \
            {lab + "_us" for lab in loop_share.LABELS}
    kinds = {k: sum(t["instr"]["by_kind"][k] for t in slices)
             for k in ("span", "section", "hook", "roll")}
    assert all(v > 0 for v in kinds.values())
