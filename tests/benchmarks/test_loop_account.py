"""The readers of the loop account's spans and of the offload hand-offs,
on hand-built spans and in a tiny traced run of each cell; the gaps of a
trace by layer on hand-built slices."""
from __future__ import annotations

import os
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import gap_layers, harness  # noqa: E402
from tests.benchmarks.test_benchmarks import BENCH, _tiny  # noqa: E402

LABELS = ("msgr", "client", "osd", "offload", "store", "harness",
          "background", "gc", "unattributed", "idle")
SHARES = [f"loop_{k}_pct" for k in LABELS if k not in ("background", "idle")]
NEW = SHARES + ["loop_lag_p95_ms", "offload_handoff_ms",
                "offload_device_call_ms", "ec_read_ms"]


def _reader(name):
    return harness._load_module(ROOT, "layer_metrics", name)


def _ctx(**spans):
    return types.SimpleNamespace(spans=spans)


def _slice(lag_hist=None, **us):
    tags = {k + "_us": float(us.get(k, 0.0)) for k in LABELS}
    tags.update(callbacks=10, lag_edges_ms=(1.0, 2.0, 4.0),
                lag_hist=lag_hist or [0, 0, 0, 0])
    return {"name": "loop_slice", "duration_us": sum(us.values()),
            "tags": tags}


def entries_stand(bench):
    """PR 24's twelve stand together, found by name (eleven entries
    stood before them until PR 44 retired one): a later PR's entries
    come after. The cells an entry lists begin with the one it was
    entered for; PR 41 appended those that reported it under a name of
    their own, or had the work and no line for it, PR 44 the recovery
    cell; a later cell may join a list."""
    names = [m["name"] for m in bench["per_layer"]]
    at = names.index(NEW[0])
    assert names[at:at + 12] == NEW
    assert names[at - 1] == "store_bytes_per_user_byte"
    by = {m["name"]: m for m in bench["per_layer"]}
    assert all("workloads" not in by[n] for n in SHARES
               if n != "loop_offload_pct")
    for n in ("offload_handoff_ms", "offload_device_call_ms"):
        assert by[n]["workloads"][0] == "rb4m_write"
        # they take every batch's hops, of whatever kind: not for a
        # window that both encodes and decodes
        assert "rb4m_recovery_write" not in by[n]["workloads"]
    assert by["loop_offload_pct"]["workloads"][:3] == [
        "rb4m_write", "rb4m_degraded_seqread", "rb4m_scrub_seqread"]
    assert "rb4m_recovery_write" in by["loop_offload_pct"]["workloads"]
    assert by["ec_read_ms"]["workloads"][:4] == [
        "rb4m_seqread", "rb4m_degraded_seqread", "rb4m_scrub_seqread",
        "rb4m_fastread_seqread"]
    assert all(by[n]["source"] == "program_span" for n in NEW)


def test_the_twelve_entries_are_appended_and_nothing_else_moved():
    entries_stand(BENCH)


@pytest.mark.parametrize("name", NEW)
def test_reader_finds_nothing_on_a_program_without_its_spans(name):
    """The parent commit has no `loop_slice` span and no hop tags: the
    reader returns None there, and on spans that lack its tags."""
    mod = _reader(name)
    assert mod.read(_ctx()) is None
    bare = {"name": "x", "duration_us": 10.0, "tags": {}}
    assert mod.read(_ctx(loop_slice=[bare], offload_batch=[bare])) is None


@pytest.mark.parametrize("label", [n[5:-4] for n in SHARES])
def test_share_is_the_labels_microseconds_over_all_of_them(label):
    """Two slices; what is no label's tag counts for nothing."""
    a = _slice(msgr=300.0, idle=100.0, **{label: 200.0}
               if label != "msgr" else {})
    b = _slice(osd=100.0, gc=50.0, background=250.0)
    total = sum(v for s in (a, b) for k, v in s["tags"].items()
                if k[:-3] in LABELS)
    mine = a["tags"][label + "_us"] + b["tags"][label + "_us"]
    got = _reader(f"loop_{label}_pct").read(_ctx(loop_slice=[a, b]))
    assert got == pytest.approx(100.0 * mine / total)
    assert total == pytest.approx(1000.0 if label != "msgr" else 800.0)


def test_lag_p95_interpolates_inside_the_histograms_bucket():
    mod = _reader("loop_lag_p95_ms")
    # 100 ticks: 90 under 1 ms, 10 between 2 and 4 ms; the 95th lies
    # halfway through the latter
    a = _slice(lag_hist=[50, 0, 4, 0])
    b = _slice(lag_hist=[40, 0, 6, 0])
    assert mod.read(_ctx(loop_slice=[a, b])) == pytest.approx(3.0)
    late = _slice(lag_hist=[0, 0, 0, 10])       # the open bucket: 4-8 ms
    assert mod.read(_ctx(loop_slice=[late])) == pytest.approx(7.8)


def test_offload_medians_are_taken_per_batch():
    def batch(sem, pool, resume, h2d, launch, wait):
        return {"name": "offload_batch", "duration_us": 1.0, "tags": {
            "sem_wait_us": sem, "stack_us": 9e9, "pool_wait_us": pool,
            "h2d_submit_us": h2d, "launch_us": launch,
            "result_wait_us": wait, "resume_us": resume,
            "scatter_us": 9e9}}
    spans = [batch(100, 200, 300, 1000, 10, 2000),
             batch(0, 100, 100, 500, 10, 500),
             batch(5000, 500, 500, 4000, 90, 4000),
             {"name": "offload_batch", "duration_us": 1.0,
              "tags": {"device": "host"}}]        # a host batch: no hops
    ctx = _ctx(offload_batch=spans)
    assert _reader("offload_handoff_ms").read(ctx) == pytest.approx(0.6)
    assert _reader("offload_device_call_ms").read(ctx) == \
        pytest.approx(3.01)
    reads = [{"name": "ec_read", "duration_us": d, "tags": {}}
             for d in (1000.0, 9000.0, 3000.0)]
    assert _reader("ec_read_ms").read(_ctx(ec_read=reads)) == \
        pytest.approx(3.0)


def test_tiny_traced_seqread_reports_the_loop_and_the_read_path(tmp_path):
    """The cell whose path had no span: `ec_read` on the primary with
    its rounds, and a loop whose labels, with `background` and `idle`,
    are all of its time."""
    done, cell = _tiny("rb4m_seqread", trace=True, tmp=tmp_path)
    line = done["result"]
    assert line["correct"] is True
    m = {k: v["value"] for k, v in line["metrics"].items()}
    assert set(m) == {r.NAME for r in cell.readers} - \
        {"device_idle_pct", "apply_bitmatrix_batched_roofline"}
    assert {"ec_read_ms", "loop_msgr_pct", "loop_lag_p95_ms"} <= set(m)
    assert "loop_offload_pct" not in m and "offload_handoff_ms" not in m
    assert m["ec_read_ms"] > 0
    busy = sum(m[n] for n in SHARES if n in m)
    assert 0 < busy <= 100.5
    assert m["loop_msgr_pct"] > 5 and m["loop_osd_pct"] > 5
    assert m["loop_unattributed_pct"] < 10


def test_tiny_traced_write_reports_the_hand_offs(tmp_path):
    done, _cell = _tiny("rb4m_write", trace=True, tmp=tmp_path)
    m = {k: v["value"] for k, v in done["result"]["metrics"].items()}
    assert set(NEW) - {"ec_read_ms"} <= set(m)
    assert m["offload_handoff_ms"] > 0
    assert m["offload_device_call_ms"] > 0
    # what is inside the encode span cannot be longer than it
    assert m["offload_device_call_ms"] < 3 * m["ec_encode_ms"]


# -- the gaps of a trace by layer ---------------------------------------------

def test_gap_make_up_counts_edge_slices_pro_rata():
    ms = 1e6                                        # ns
    slices = [(0 * ms, 50 * ms, {"msgr": 30_000.0, "idle": 20_000.0}),
              (50 * ms, 100 * ms, {"osd": 50_000.0}),
              (100 * ms, 150 * ms, {"harness": 10_000.0,
                                    "idle": 40_000.0})]
    # from the middle of the first slice to a fifth into the third
    by = gap_layers.make_up(25 * ms, 110 * ms, slices)
    assert by["msgr"] == pytest.approx(0.015)
    assert by["osd"] == pytest.approx(0.050)
    assert by["harness"] == pytest.approx(0.002)
    assert by["idle"] == pytest.approx(0.010 + 0.008)
    assert sum(by.values()) == pytest.approx(0.085)
    assert gap_layers.make_up(200 * ms, 300 * ms, slices) == {}


def _ev(name, start, dur, **stats):
    return types.SimpleNamespace(name=name, start_ns=start,
                                 duration_ns=dur, stats=list(stats.items()))


def test_gaps_are_found_as_the_reduction_finds_them_and_marks_reach_back():
    host = types.SimpleNamespace(name="/host:CPU", lines=[
        types.SimpleNamespace(name="python3", events=[
            _ev("bench_window", 1000.0, 9000.0),
            _ev("loop_slice50", 6000.0, 1.0, len_us=5, msgr_us=3, idle_us=2),
            _ev("loop:osd", 9000.0, 1.0, dur_us=2, pc_ns=1),
            _ev("Execute", 2000.0, 10.0)])])
    dev = types.SimpleNamespace(name="/device:TPU:0", lines=[
        types.SimpleNamespace(name="XLA Ops", events=[
            _ev("%f = s32[1] fusion()", 2000.0, 500.0),
            _ev("%g = s32[1] fusion()", 8000.0, 500.0)])])
    window, gaps = gap_layers.gaps_of([host, dev])
    assert window == (1000.0, 10000.0)
    assert gaps == [(2500.0, 8000.0), (8500.0, 10000.0), (1000.0, 2000.0)]
    slices, marks = gap_layers.slices_and_marks([host, dev])
    assert slices == [(1000.0, 6000.0, {"msgr": 3.0, "idle": 2.0})]
    assert marks == [("loop:osd", 7000.0, 9000.0)]
    by = gap_layers.make_up(*gaps[0], slices)
    assert by["msgr"] == pytest.approx(3e-6 * 3500 / 5000)
