"""The cell `rb4m_fastread_seqread` (configuration
`radosbench_ec83_tpu_fastread`): its entries, files and readers, the
plain reference's own rules, and the cell served tiny on the CPU
backend, where it must be correct, reconstruct, and come out incorrect
under both controls."""
from __future__ import annotations

import ast
import json
import os
import types

import pytest

from tests.benchmarks.test_benchmarks import BENCH, ROOT, _tiny
from benchmarks import harness, reference_fastread

CONFIG = "radosbench_ec83_tpu_fastread"
CELL = "rb4m_fastread_seqread"
NEW = ["fastread_decode_pct", "fastread_mean_r", "fastread_decode_patterns",
       "fastread_matrix_misses", "fastread_late_replies_pct",
       "dispatch_held_pct"]
#: accepted entries (the read cell's one, the degraded cell's six)
#: that list this cell since PR 41; before it, readers of this cell's
#: own imported them as `<name>.fastread`
FOLDED = ["ec_read_ms", "ec_decode_ms", "decode_ops_per_batch",
          "decode_handoff_ms", "decode_device_call_ms",
          "decode_link_bytes_per_byte", "decode_bitmatrix_roofline"]
FROM_TRACE = {"device_idle_pct", "decode_bitmatrix_roofline"}
K, M = 8, 3


def _reader(name):
    return harness._load_module(ROOT, "layer_metrics", name)


# -- BENCHMARK.json and the files it names --------------------------------------------

def entries_stand(bench, root=ROOT):
    """PR 35 appended them after the stores' two shares (PR 34); PR 41
    took out four entries before them and this cell's seven renamed
    readers after, so the place is found by name. Three configurations
    and four cells stood before this one; a later PR's come after, and
    a later cell may join a list."""
    entries = bench["per_layer"]
    names = [m["name"] for m in entries]
    at = names.index(NEW[0])
    assert names[at - 2:at] == ["store_write_direct_pct",
                                "store_read_direct_pct"]
    assert names[at:at + 6] == NEW
    assert names[at + 6] == "msgr_acks_carried_pct"
    for m in entries[at:at + 6]:
        assert CELL in m["workloads"]
        mod = _reader(m["name"])
        assert (mod.NAME, mod.UNIT, mod.LAYER, mod.MOVES) == \
            (m["name"], m["unit"], m["layer"], m["moves"])
    assert [m["name"] for m in entries[:at]
            if CELL in m.get("workloads", [])] == FOLDED
    layers = {m["layer"] for m in entries[:at]}
    assert {m["layer"] for m in entries[at:at + 6]} <= layers
    assert [c["name"] for c in bench["configs"]][:4] == [
        "radosbench_ec83_tpu", "radosbench_ec83_tpu_degraded",
        "radosbench_ec83_tpu_scrub", CONFIG]
    assert [w["name"] for w in bench["workloads"]][:5] == [
        "rb4m_write", "rb4m_seqread", "rb4m_degraded_seqread",
        "rb4m_scrub_seqread", CELL]
    entry = {c["name"]: c for c in bench["configs"]}[CONFIG]
    assert len(entry["source"]) <= 200 and len(entry["why"]) <= 200
    cell = {w["name"]: w for w in bench["workloads"]}[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        (CONFIG, CELL, 1)
    # the cell loads its readers, and no other cell loads them
    loaded = {r.NAME for r in harness.load_cell(CELL, root=root).readers}
    assert set(NEW + FOLDED) <= loaded
    for w in bench["workloads"]:
        if w["name"] != CELL:
            other = {r.NAME for r in harness.load_cell(
                w["name"], root=root).readers}
            assert not other & set(NEW)


def test_the_entries_stand_in_their_order_after_what_stood_before_them():
    entries_stand(BENCH)


def test_configuration_and_traffic_hold_the_deployments_keys():
    entry = {c["name"]: c for c in BENCH["configs"]}[CONFIG]
    assert len(entry["source"]) <= 200 and len(entry["why"]) <= 200
    assert "fast_read" in entry["source"] and \
        "osd-dispatch-delay.yaml" in entry["source"]
    body = json.load(open(os.path.join(ROOT, entry["file"])))
    sibling = json.load(open(os.path.join(
        ROOT, "benchmarks", "configs", "radosbench_ec83_tpu.json")))
    assert body["name"] == CONFIG and body["source"] == entry["source"]
    for key in ("generator", "object_size", "concurrent_ops", "pool", "osds",
                "offload_service", "hosts", "objectstore"):
        assert body[key] == sibling[key], key
    # the pool's default is the mon's, in force when the pool is made
    assert body["mon_config"] == {"osd_pool_default_ec_fast_read": True}
    assert "mon_config" not in sibling
    assert body["osd_config"] == {
        "osd_debug_inject_dispatch_delay_probability": 0.1,
        "osd_debug_inject_dispatch_delay_duration": 0.1,
        "osd_scrub_interval": 86400.0, "osd_heartbeat_grace": 20.0}
    assert sorted(body["reduced"]) == sorted(entry["reduced"]) == \
        ["hosts", "object_count", "objectstore"]
    assert set(sibling["guarantees"]) < set(body["guarantees"])
    assert set(body["guarantees"]) - set(sibling["guarantees"]) == \
        {"fast_read", "decodes_served_by"}
    assert "first k chunks of one version" in body["guarantees"]["fast_read"]
    assert body["guarantees"]["durability"].startswith("none")
    assert "fast_read_default_on_the_osd" not in body["assumed"]
    for key in ("where_the_delay_is_consulted", "values_and_fragment_names",
                "thrasher_left_out", "seq_wraps"):
        assert key in body["assumed"], key
    traffic = json.load(open(os.path.join(
        ROOT, "benchmarks", "traffic", CELL + ".json")))
    assert traffic == {"op": "seq", "clients": 16, "preload_objects": 128,
                       "warmup_ops": 64, "payload_pool": 64}
    cell = {w["name"]: w for w in BENCH["workloads"]}[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        (CONFIG, CELL, 1)
    assert len(cell["why"]) <= 200
    # the share the file states is the reference's arithmetic
    assert "0.5217" in body["expected_decode_share"]
    assert reference_fastread.expected_decode_share(
        body["osd_config"]["osd_debug_inject_dispatch_delay_probability"],
        body["pool"]["k"]) == pytest.approx(0.5217, abs=5e-5)


def test_the_reference_imports_nothing_of_the_program():
    path = os.path.join(ROOT, "benchmarks", "reference_fastread.py")
    tree = ast.parse(open(path).read())
    imported = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            imported |= {a.name for a in node.names}
        elif isinstance(node, ast.ImportFrom):
            imported.add(node.module)
    assert imported == {"__future__", "numpy", "benchmarks.reference_decode"}


def test_the_cell_loads_its_readers_and_no_other_cells():
    cell = harness.load_cell(CELL)
    names = {r.NAME for r in cell.readers}
    assert set(NEW + FOLDED) <= names
    # neither of the stores' shares lists this cell (its reads are the
    # read cell's, its decodes the degraded cell's: nothing of its own
    # to say there), nor the degraded cell's share of reads that decode,
    # which `fastread_decode_pct` is here
    assert not {"store_read_direct_pct", "store_write_direct_pct",
                "degraded_read_pct"} & names
    assert not any(n.endswith(".fastread") for n in names)
    for w in BENCH["workloads"]:
        if w["name"] != CELL:
            other = {r.NAME for r in harness.load_cell(w["name"]).readers}
            assert not other & set(NEW)


# -- the readers on hand-built spans -------------------------------------------------------

HOPS = {"sem_wait_us": 100.0, "pool_wait_us": 200.0, "resume_us": 300.0,
        "h2d_submit_us": 400.0, "launch_us": 500.0, "result_wait_us": 600.0}


def _span(name, us=1000.0, **tags):
    return {"name": name, "duration_us": us, "tags": tags}


def _batch(kind=None, **tags):
    t = {**HOPS, "ops": 1, "bytes": 4 << 20, "device": "tpu:0", **tags}
    if kind is not None:
        t["kind"] = kind
    return _span("offload_batch", 5000.0, **t)


def _ctx(spans=None, offload=(None, None), copy=(None, None), trace=None,
         peaks=None, read_bytes=0):
    groups = [{}, {}]
    for side in (0, 1):
        if offload[side] is not None:
            groups[side]["offload"] = offload[side]
        if copy[side] is not None:
            groups[side]["copy"] = copy[side]
    return types.SimpleNamespace(
        spans=spans or {}, open=groups[0], close=groups[1], trace=trace,
        peaks=peaks, window_s=10.0, user_bytes={"read": read_bytes},
        cell=types.SimpleNamespace(
            config={"pool": {"k": K, "m": M, "pg_num": 32}}))


PEAKS = {"hbm_bytes_per_s": 819e9, "int8_ops_per_s": 393e12}
TRACE = {"programs": {"jit__apply_bitmatrix_batched_jit": 0.002},
         "busy_s": 0.002, "window_s": 10.0}
PARENT_READS = [_span("ec_read", 90000.0, bytes=4 << 20, shards_asked=7,
                      rounds=1)] * 5


@pytest.mark.parametrize("name", NEW + FOLDED)
@pytest.mark.parametrize("case", ["nothing", "untagged_spans"])
def test_reader_finds_nothing_on_a_program_without_the_tags(name, case):
    """A program without `fast_read` opens `ec_read` spans that are not
    tagged `fast`, holds nothing back, decodes nothing in a healthy
    pool and builds no codec: every reader of this cell's own returns
    None there, and so do the degraded cell's that it shares
    (`ec_read_ms` reads the spans the parent opens too: None only
    where there are none)."""
    ctx = {
        "nothing": _ctx(),
        "untagged_spans": _ctx(
            spans={"ec_read": PARENT_READS,
                   "osd_op": [_span("osd_op", queue_wait_us=5.0)] * 5,
                   "offload_batch": [_batch(), _batch("enc")],
                   "tpu_decode_dispatch": []},
            trace=TRACE, peaks=PEAKS),
    }[case]
    got = _reader(name).read(ctx)
    if name == "ec_read_ms" and case == "untagged_spans":
        assert got == pytest.approx(90.0)
    else:
        assert got is None


def test_fastread_readers_read_the_spans():
    reads = [_span("ec_read", fast=True, shards_asked=10, late=3,
                   shards_used=[0, 1, 2, 3, 4, 5, 6, 7])] * 5 \
        + [_span("ec_read", fast=True, shards_asked=10, late=2,
                 shards_used=[0, 1, 2, 3, 4, 5, 6, 8])] * 3 \
        + [_span("ec_read", shards_asked=7, rounds=1)] * 2
    decodes = [_span("ec_decode", missing=[7])] * 2 \
        + [_span("ec_decode", missing=[3, 7])] \
        + [_span("ec_decode", missing=[1, 2, 5])]
    batches = [_batch("dec", r=1, pattern="0,1,2,3,4,5,6,8>7"),
               _batch("dec", r=1, pattern="0,1,2,3,4,5,6,8>7"),
               _batch("dec", r=2, pattern="0,1,2,4,5,6,8,9>3,7"),
               _batch("enc"), _batch()]
    dispatches = [_span("tpu_decode_dispatch", matrix_miss=True),
                  _span("tpu_decode_dispatch", matrix_miss=False),
                  _span("tpu_decode_dispatch", matrix_miss=True),
                  _span("tpu_decode_dispatch")]
    ctx = _ctx(spans={
        "ec_read": reads, "ec_decode": decodes, "offload_batch": batches,
        "tpu_decode_dispatch": dispatches,
        "osd_op": [_span("osd_op")] * 10,
        "dispatch_hold": [_span("dispatch_hold", 100000.0, kind="subop")] * 9
        + [_span("dispatch_hold", 100000.0, kind="op")]})
    assert _reader("fastread_decode_pct").read(ctx) == pytest.approx(50.0)
    assert _reader("fastread_mean_r").read(ctx) == pytest.approx(7 / 4)
    assert _reader("fastread_decode_patterns").read(ctx) == 2.0
    assert _reader("fastread_matrix_misses").read(ctx) == 2.0
    assert _reader("fastread_late_replies_pct").read(ctx) == \
        pytest.approx(100 * 21 / 80)
    # ten ops and 94 sub-reads were dequeued, ten were held
    assert _reader("dispatch_held_pct").read(ctx) == \
        pytest.approx(100 * 10 / 104)
    # fast reads that all met their data positions first: a share of 0,
    # a true reading, where a cell without fast reads has none
    healthy = _ctx(spans={"ec_read": reads[:5]})
    assert _reader("fastread_decode_pct").read(healthy) == 0.0
    assert _reader("fastread_mean_r").read(healthy) is None
    assert _reader("fastread_late_replies_pct").read(healthy) == 30.0
    # codecs all found: 0 misses, a true reading too
    warm = _ctx(spans={"tpu_decode_dispatch": dispatches[1:2]})
    assert _reader("fastread_matrix_misses").read(warm) == 0.0


@pytest.mark.parametrize("name", FOLDED)
def test_an_accepted_entry_lists_this_cell(name):
    """The degraded cell's readers and `ec_read_ms` list their cells.
    Until PR 41 this one reported them as `<name>.fastread` through
    readers of its own that imported the accepted ones; now the
    accepted entries name the cell, after the cells they named before
    (a later cell may come after it), and neither those entries nor
    those files are left."""
    by = {m["name"]: m for m in BENCH["per_layer"]}
    assert CELL in by[name]["workloads"]
    assert "rb4m_degraded_seqread" in by[name]["workloads"]
    assert name + ".fastread" not in by
    with pytest.raises(SystemExit):
        _reader(name + ".fastread")
    assert name in {r.NAME for r in harness.load_cell(CELL).readers}


def test_the_roofline_reckons_each_batch_at_its_true_r():
    """Batches of r = 1, 2 and 3 in one window, and no encode: the
    share is the sum of each batch's least time at its own r over the
    program's device time, and it is under 100."""
    from benchmarks.layer_metrics.apply_bitmatrix_batched_roofline import (
        least_seconds)
    mod = _reader("decode_bitmatrix_roofline")
    batches = [_batch("dec", r=r, pattern=f"p{r}") for r in (1, 1, 2, 3)]
    ctx = _ctx(spans={"offload_batch": batches}, trace=TRACE, peaks=PEAKS)
    least = sum(max(least_seconds(4 << 20, K, r, PEAKS).values())
                for r in (1, 1, 2, 3))
    assert mod.read(ctx) == pytest.approx(100 * least / 0.002)
    assert 0 < mod.read(ctx) < 100
    # a window that also encodes has no device time of the decodes' own
    ctx = _ctx(spans={"offload_batch": batches + [_batch("enc")]},
               trace=TRACE, peaks=PEAKS)
    assert mod.read(ctx) is None


# -- the cell, served tiny ---------------------------------------------------------------------

@pytest.fixture(scope="module")
def served(tmp_path_factory):
    seen: dict = {}
    real_spans = harness.window_spans

    def window_spans(spans):
        seen["spans"] = real_spans(spans)
        return seen["spans"]
    harness.window_spans = window_spans
    try:
        done, cell = _tiny(CELL, trace=True, seconds=3.0,
                           tmp=tmp_path_factory.mktemp("served"))
    finally:
        harness.window_spans = real_spans
    return done, cell, seen


def test_tiny_served_run_is_correct_and_reconstructs(served):
    done, cell, seen = served
    line = done["result"]
    assert line["correct"] is True and line["failed"] == 0
    assert all(value <= limit for _n, value, limit in done["checks"])
    m = {k: v["value"] for k, v in line["metrics"].items()}
    assert set(m) == {r.NAME for r in cell.readers} - FROM_TRACE
    assert set(NEW + FOLDED) - FROM_TRACE <= set(m)
    assert m["compiles_in_window"] == 0
    # k=2 m=1 here: a read asks both peers and answers from the first
    assert 0 < m["fastread_decode_pct"] < 100
    assert m["fastread_mean_r"] == 1.0
    assert m["fastread_late_replies_pct"] == 50.0
    assert 1 <= m["fastread_decode_patterns"] <= 3
    assert m["fastread_matrix_misses"] >= 0
    assert 5.0 <= m["dispatch_held_pct"] <= 15.0
    assert m["decode_ops_per_batch"] >= 1.0
    assert m["ec_read_ms"] > 0 and m["ec_decode_ms"] > 0
    assert m["decode_handoff_ms"] > 0
    assert m["decode_device_call_ms"] > 0
    assert 0 < m["decode_link_bytes_per_byte"] < 1.5
    # neither of the stores' shares: their entries do not list this cell
    assert not {"store_read_direct_pct", "store_write_direct_pct"} & set(m)
    reads = [s["tags"] for s in seen["spans"]["ec_read"]]
    assert reads and all(t["fast"] is True and t["shards_asked"] == 2
                         and t["late"] == 1 and t["rounds"] == 1
                         and len(t["shards_used"]) == 2 for t in reads)
    decodes = [s["tags"] for s in seen["spans"]["ec_decode"]]
    assert decodes and all(t["offload"] and len(t["missing"]) == 1
                           for t in decodes)
    holds = seen["spans"]["dispatch_hold"]
    assert {s["tags"]["kind"] for s in holds} == {"op", "subop"}
    assert all(95e3 <= s["duration_us"] <= 400e3 for s in holds)


def test_tiny_served_run_decodes_on_the_device_and_nowhere_else(served):
    done, _cell, seen = served
    checks = {n: v for n, v, _l in done["checks"]}
    assert checks["fallback_ops"] == checks["breaker_trips"] == \
        checks["device_failovers"] == checks["lanes_off_platform"] == 0
    assert checks["osd_markdowns_under_load"] == 0
    batches = [s["tags"] for s in seen["spans"]["offload_batch"]]
    assert batches and {t["kind"] for t in batches} == {"dec"}
    assert all(t["device"] != "host" and t["r"] == 1 for t in batches)
    tagged = [s["tags"] for s in seen["spans"]["tpu_decode_dispatch"]]
    assert tagged and all(isinstance(t["matrix_miss"], bool)
                          for t in tagged)


def test_both_controls_make_the_cell_incorrect(tmp_path):
    done, _ = _tiny(CELL, control=("bitrot", "flip_read"), tmp=tmp_path)
    checks = {n: v for n, v, _l in done["checks"]}
    assert checks["shard_bytes_differing"] == 1
    assert checks["sample_read_mismatches"] + checks["read_mismatches"] == 1
    assert done["result"]["correct"] is False


@pytest.mark.parametrize("control", ["bitrot", "flip_read"])
def test_each_control_alone_makes_the_cell_incorrect(control, tmp_path):
    done, _ = _tiny(CELL, control=(control,), tmp=tmp_path)
    assert done["result"]["correct"] is False
    sound = {"fallback_ops", "breaker_trips", "device_failovers"}
    assert all(v == 0 for n, v, _l in done["checks"] if n in sound)
