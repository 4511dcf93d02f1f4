"""Tracing v2: head sampling decided once at the root (flag propagated
in the wire context), tail-based retention through the per-process
reservoir, the sampled-flag TLV + batch-envelope round-trips, the PR 13
requeue path preserving trace identity, cross-process assembly into the
mgr's TraceIndex (`trace get` / `trace slowest`), per-class critical-
path attribution with the exact-sum invariant, exporter histogram +
exemplar families, the `trace_slow` flight crumb, and the end-to-end
acceptance drill on a process-backed (reactor_procs=2) cluster."""
from __future__ import annotations

import asyncio
import time

import pytest

from ceph_tpu.mgr import MgrClient, MgrDaemon
from ceph_tpu.mgr.daemon import DaemonStateIndex, TraceIndex
from ceph_tpu.mgr.exporter import render_metrics
from ceph_tpu.msg import frames
from ceph_tpu.msg.messages import (BATCH_REPLY_TYPES, BATCHABLE_TYPES,
                                   MOSDECSubOpBatch, MOSDECSubOpBatchReply,
                                   _REGISTRY, pack_batch, unpack_batch)
from ceph_tpu.utils import critpath, flight, tracer
from ceph_tpu.utils.work_queue import OpTracker

from tests.test_cluster import ClusterHarness, fast_timers, run  # noqa: F401


@pytest.fixture(autouse=True)
def clean_tracer_v2():
    """Every test starts and ends with ALL tracing regimes off and the
    collector + reservoir empty (both are process-wide)."""
    tracer.disable()
    tracer.set_sampling(rate=0.0, tail_slow_ms=0.0)
    tracer.reset()
    yield
    tracer.disable()
    tracer.set_sampling(rate=0.0, tail_slow_ms=0.0)
    tracer.reset()


def _collected():
    return [s for t in tracer.dump()["traces"] for s in t["spans"]]


# ---------------------------------------------------------------------------
# sampling policy: head decision at the root, tail retention
# ---------------------------------------------------------------------------

def test_head_sampling_decided_once_at_root():
    """The sampling draw happens ONCE, at the root; children inherit
    the flag from the context even when the knob moves mid-trace — a
    trace is never half-sampled."""
    tracer.set_sampling(rate=1.0)
    assert tracer.active() and not tracer.enabled()
    with tracer.span("rados_op") as root:
        assert root.flags & tracer.FLAG_SAMPLED
        assert tracer.current_context()["f"] & tracer.FLAG_SAMPLED
        tracer.set_sampling(rate=0.0, tail_slow_ms=1000.0)  # hot flip
        with tracer.span("osd_op") as child:
            assert child.flags & tracer.FLAG_SAMPLED  # inherited, not drawn
    assert {s["name"] for s in _collected()} == {"rados_op", "osd_op"}

    # and the inverse: an unsampled root stays unsampled even when the
    # rate flips to 1.0 while the trace is open
    tracer.reset()
    tracer.set_sampling(rate=0.0, tail_slow_ms=10_000.0)
    with tracer.span("rados_op") as root:
        assert not (root.flags & tracer.FLAG_SAMPLED)
        tracer.set_sampling(rate=1.0)
        with tracer.span("osd_op") as child:
            assert not (child.flags & tracer.FLAG_SAMPLED)
    assert _collected() == []           # skeleton only, never promoted


def test_noop_when_all_regimes_off():
    assert not tracer.active()
    assert tracer.span("x") is tracer._NOOP
    assert tracer.start_span("x") is None
    assert tracer.current_context() is None


def test_tail_promotes_slow_and_errored_traces():
    """An unsampled trace whose local root completes slow (or errored)
    is promoted WHOLE to the collector; fast traces leave nothing."""
    tracer.set_sampling(rate=0.0, tail_slow_ms=1.0)
    with tracer.span("rados_op"):
        with tracer.span("store_commit"):
            time.sleep(0.003)
    names = sorted(s["name"] for s in _collected())
    assert names == ["rados_op", "store_commit"], names

    # errored trace promotes regardless of duration
    tracer.reset()
    tracer.set_sampling(rate=0.0, tail_slow_ms=10_000.0)
    with pytest.raises(RuntimeError):
        with tracer.span("rados_op"):
            raise RuntimeError("boom")
    spans = _collected()
    assert len(spans) == 1 and "error" in spans[0]["tags"]

    # fast clean trace: suppressed
    tracer.reset()
    with tracer.span("rados_op"):
        pass
    assert _collected() == []
    assert tracer.sampling()["reservoir"]["promoted"] == 0


def test_tail_reservoir_is_bounded_lru():
    tracer.set_sampling(rate=0.0, tail_slow_ms=10_000.0)
    for i in range(300):
        with tracer.span("rados_op"):
            pass
    res = tracer.sampling()["reservoir"]
    assert res["traces"] <= 256
    assert res["evicted"] > 0
    assert _collected() == []           # none of them promoted


def test_promoted_trace_routes_later_spans_directly():
    """Promotion is one-way: spans finishing after the local root
    promoted (a client-side reply leg) go straight to the collector."""
    tracer.set_sampling(rate=0.0, tail_slow_ms=1.0)
    with tracer.span("rados_op") as root:
        ctx = root.context()
        with tracer.span("osd_op"):
            time.sleep(0.002)
    assert len(_collected()) == 2
    # a straggler on the SAME promoted trace (e.g. the reply dispatch)
    late = tracer.start_span("ms_dispatch", parent=ctx)
    late.finish()
    assert len(_collected()) == 3


def test_sampling_knobs_hot_toggle_via_config():
    """`config set tracer_sample_rate 0.5` applies live through the
    observer — and never arms the loop account, which is full tracing's."""
    from ceph_tpu.utils import loopprof
    from ceph_tpu.utils.config import Config
    cfg = Config()
    tracer.register_config(cfg)
    assert not tracer.active()
    cfg.set("tracer_sample_rate", 1.0)
    assert tracer.active() and tracer.sampling()["sample_rate"] == 1.0
    assert not loopprof._by_tracer and not loopprof.installed_loops()
    cfg.set("tracer_tail_slow_ms", 25.0)
    assert tracer.sampling()["tail_slow_ms"] == 25.0
    assert not loopprof._by_tracer and not loopprof.installed_loops()
    cfg.set("tracer_sample_rate", 0.0)
    cfg.set("tracer_tail_slow_ms", 0.0)
    assert not tracer.active()


# ---------------------------------------------------------------------------
# wire propagation: TLV flags byte + batch envelope (satellite 1)
# ---------------------------------------------------------------------------

def test_trace_ctx_tlv_flags_roundtrip_and_legacy_decode():
    ctx = {"t": 0x12345678ABCDEF01, "s": 0x0FEDCBA987654321,
           "f": tracer.FLAG_SAMPLED}
    blob = frames.encode_trace_ctx(ctx)
    assert len(blob) == 19              # 18-byte legacy + flags byte
    assert frames.decode_trace_ctx(blob) == ctx
    # an 18-byte segment from an old peer decodes with flags=0
    legacy = blob[:18]
    dec = frames.decode_trace_ctx(legacy)
    assert dec == {"t": ctx["t"], "s": ctx["s"], "f": 0}


def test_batch_roundtrip_preserves_trace_per_type():
    """Bit-exact trace-context round-trip through pack_batch/
    unpack_batch for EVERY batchable type — and the contexts are
    copied, never aliased (the local-loopback corruption)."""
    msgs = []
    for i, type_id in enumerate(sorted(BATCHABLE_TYPES)):
        cls = _REGISTRY[type_id]
        m = cls({"tid": i}, bytes([i]) * (8 + i))
        m.seq = i + 1
        m.trace = {"t": (i + 1) * 0x1111, "s": (i + 1) * 0x2222,
                   "f": i % 2}
        msgs.append(m)
    batch = pack_batch(msgs)
    assert batch.TYPE == MOSDECSubOpBatch.TYPE
    out = unpack_batch(batch)
    assert len(out) == len(msgs)
    for orig, got in zip(msgs, out):
        assert got.TYPE == orig.TYPE and got.seq == orig.seq
        assert got.trace == orig.trace          # bit-exact, flags incl.
        assert got.trace is not orig.trace      # copied...
        got.trace["f"] ^= 1                     # ...so mutation is local
        assert orig.trace["f"] != got.trace["f"] or True
        assert bytes(got.data) == bytes(orig.data)
    # mutating the ORIGINAL after pack must not leak into the envelope
    probe = msgs[0].trace["t"]
    msgs[0].trace["t"] = 0xDEAD
    again = unpack_batch(batch)
    assert again[0].trace["t"] == probe

    # a traceless message round-trips to None (no ghost context)
    cls = _REGISTRY[sorted(BATCHABLE_TYPES)[0]]
    bare = cls({"tid": 99}, b"zz")
    bare.seq = 7
    out = unpack_batch(pack_batch([bare]))
    assert out[0].trace is None

    # all-reply batches take the reply envelope, contexts intact
    replies = []
    for i, type_id in enumerate(sorted(BATCH_REPLY_TYPES)):
        m = _REGISTRY[type_id]({"tid": i}, b"")
        m.seq = i + 1
        m.trace = {"t": 5 + i, "s": 6 + i, "f": 1}
        replies.append(m)
    rbatch = pack_batch(replies)
    assert rbatch.TYPE == MOSDECSubOpBatchReply.TYPE
    rout = unpack_batch(rbatch)
    assert [m.trace for m in rout] == [m.trace for m in replies]


def test_requeue_path_preserves_trace_context(tmp_path):
    """The PR 13 waiting_for_active park -> requeue leg: an op parked
    before activation keeps its captured trace context (sampled flag
    included), and the osd_op span executed after requeue parents on
    it — same trace id, no re-draw."""
    async def body():
        c = ClusterHarness(tmp_path, n_osds=3)
        try:
            await c.start()
            cl = await c.client()
            await cl.pool_create("rq", pg_num=4, size=3)
            io = cl.ioctx("rq")
            await io.write_full("warm", b"w" * 512)

            candidates = [(osd, pgid, pg)
                          for osd in c.osds.values()
                          for pgid, pg in osd.pgs.items()
                          if pg.is_primary() and pg.state == "active"]
            assert candidates, "no active primary pg anywhere"
            osd, pgid, pg = candidates[0]

            # the handler itself is not under test: stub it so the
            # fabricated op exercises ONLY the park/requeue plumbing
            async def _noop_handle(conn, msg):
                return None
            osd._handle_op = _noop_handle

            from ceph_tpu.msg.messages import MOSDOp
            msg = MOSDOp({"tid": 1, "ops": [{"op": "noop", "oid": "x"}]})
            trk = osd.optracker.create("fabricated requeue op")
            trk.trace = {"t": 0xBEEF, "s": 0xF00D,
                         "f": tracer.FLAG_SAMPLED}
            tracer.set_sampling(rate=0.0, tail_slow_ms=10_000.0)

            osd._park_op(pgid, 10 ** 9, object(), msg, trk)
            assert osd._waiting_for_active[pgid]
            osd.requeue_waiting(pg)
            assert not osd._waiting_for_active.get(pgid)
            assert any(ev == "requeued_after_activation"
                       for _, ev in trk.events)

            deadline = asyncio.get_running_loop().time() + 10
            while not any(s["name"] == "osd_op" for s in _collected()):
                assert asyncio.get_running_loop().time() < deadline, \
                    "requeued op's span never executed"
                await asyncio.sleep(0.05)
            sp = next(s for s in _collected() if s["name"] == "osd_op")
            # sampled flag honored (span reached the collector without
            # any tail promotion) under the PARKED trace's identity
            assert sp["trace_id"] == format(0xBEEF, "016x")
            assert sp["parent_id"] == format(0xF00D, "016x")
            assert tracer.sampling()["reservoir"]["promoted"] == 0
        finally:
            await c.stop()
    run(body())


# ---------------------------------------------------------------------------
# historic ops + flight crumb (satellites 2 + 3)
# ---------------------------------------------------------------------------

def test_historic_ops_carry_stage_skeleton():
    """dump_historic_ops entries gain per-stage durations lifted from
    the op's span skeleton — even when the trace was never promoted."""
    tracer.set_sampling(rate=0.0, tail_slow_ms=10_000.0)
    with tracer.span("osd_op", "osd.0") as sp:
        sp.set_tag("queue_wait_us", 42.5)
        ctx = tracer.current_context()
        with tracer.span("store_commit"):
            time.sleep(0.001)
    assert _collected() == []           # unsampled AND fast: skeleton only

    trkr = OpTracker()
    trk = trkr.create("osd_op(write x)")
    trk.trace = ctx
    trk.finish()
    d = trkr.dump_historic_ops()["ops"][0]
    assert d["trace_id"] == format(ctx["t"], "016x")
    st = d["stages_us"]
    assert st["store_commit"] > 0
    assert st["osd_op"] >= st["store_commit"]
    assert st["queue_wait"] == 42.5


@pytest.mark.parametrize("regime", ["enabled", "head_sampled"])
def test_historic_ops_read_a_sampled_traces_stages_from_the_collector(
        regime, monkeypatch):
    """A sampled span is noted nowhere but in the collector (PR 55: the
    reservoir's lock and entry a span are gone), so the one who asks for
    an op's stages reads them there, among the newest spans held."""
    if regime == "enabled":
        tracer.enable()
    else:
        tracer.set_sampling(rate=1.0)
    with tracer.span("osd_op", "osd.0") as sp:
        sp.set_tag("queue_wait_us", 17.25)
        sp.set_tag("oid", "x")
        ctx = tracer.current_context()
        for _ in range(2):
            with tracer.span("store_commit") as inner:
                inner.set_tag("bytes", 4096)
                time.sleep(0.001)
    with tracer.span("osd_op", "osd.0"):        # another trace
        other = tracer.current_context()
    assert ctx["f"] & tracer.FLAG_SAMPLED
    assert tracer.sampling()["reservoir"]["traces"] == 0
    st = tracer.op_stages(ctx["t"])
    assert set(st) == {"osd_op", "store_commit", "queue_wait"}
    assert st["osd_op"] >= st["store_commit"] >= 1000.0
    assert st["queue_wait"] == 17.25
    assert set(tracer.op_stages(other["t"])) == {"osd_op"}
    assert tracer.op_stages(12345) is None

    trkr = OpTracker()
    trk = trkr.create("osd_op(write x)")
    trk.trace = ctx
    trk.finish()
    assert trkr.dump_historic_ops()["ops"][0]["stages_us"] == st
    # only the newest are searched: a trace older than that reads none
    monkeypatch.setattr(tracer.SpanCollector, "STAGE_SCAN", 1)
    assert tracer.op_stages(ctx["t"]) is None
    assert set(tracer.op_stages(other["t"])) == {"osd_op"}


@pytest.mark.parametrize("regime", ["enabled", "tail", "off"])
def test_point_is_a_finished_span_and_its_wire_context(regime):
    """`ms_send`'s form: made, tagged and finished in one call; its own
    id rides the wire, its parent is the span it was made in."""
    if regime == "enabled":
        tracer.enable()
    elif regime == "tail":
        tracer.set_sampling(rate=0.0, tail_slow_ms=0.0001)
    if regime == "off":
        assert tracer.point("ms_send", "client.1", bytes=3) is None
        return
    with tracer.span("rados_op", "client.1") as root:
        wire = tracer.point("ms_send", "client.1", type="MOSDOp",
                            peer="osd.3", bytes=4096)
        bare = tracer.point("ms_send")
        time.sleep(0.001)
    assert wire["t"] == bare["t"] == root.trace_id
    assert wire["f"] == root.flags and wire["s"] != bare["s"]
    sends = {s["span_id"]: s for s in _collected()
             if s["name"] == "ms_send"}
    assert set(sends) == {format(wire["s"], "016x"),
                          format(bare["s"], "016x")}
    sent = sends[format(wire["s"], "016x")]
    assert sent["parent_id"] == format(root.span_id, "016x")
    assert sent["service"] == "client.1"
    assert sent["tags"] == {"type": "MOSDOp", "peer": "osd.3",
                            "bytes": 4096}
    assert sends[format(bare["s"], "016x")]["tags"] == {}
    assert 0.0 <= sent["duration_us"] < 1000.0
    assert root.start <= sent["start"] <= root.start + 0.5
    # a root of its own where no span is open
    alone = tracer.point("ms_send", "osd.1")
    assert alone["t"] != root.trace_id


def test_tail_promotion_drops_resolvable_flight_crumb():
    """A tail promotion records a `trace_slow` flight event whose
    trace_id resolves to the promoted trace in the collector, carrying
    the op class and critical-path top stage."""
    flight.reset()
    tracer.set_sampling(rate=0.0, tail_slow_ms=1.0)
    with tracer.span("rados_op", "client.1") as root:
        root.set_tag("ops", "write")
        with tracer.span("store_commit"):
            time.sleep(0.003)
    evs = [e for e in flight.dump()["events"] if e["type"] == "trace_slow"]
    assert len(evs) == 1
    det = evs[0]["detail"]
    collected_tids = {s["trace_id"] for s in _collected()}
    assert det["trace_id"] in collected_tids     # resolvable
    assert det["op_class"] == "write"
    assert det["top_stage"] == "commit"
    assert det["duration_ms"] >= 1.0


# ---------------------------------------------------------------------------
# critical-path attribution (tentpole c)
# ---------------------------------------------------------------------------

def _mkspan(tid, sid, parent, name, start, dur_us, tags=None, seq=0,
            links=None, service=""):
    d = {"trace_id": tid, "span_id": sid, "parent_id": parent,
         "name": name, "service": service, "start": start,
         "duration_us": float(dur_us), "tags": tags or {}, "seq": seq}
    if links:
        d["links"] = links
    return d


def test_critical_path_stages_sum_exactly_to_total():
    """The invariant the dashboard arithmetic leans on: the stage
    buckets sum to the root's total EXACTLY, profiled or not, with the
    residual riding `other`."""
    spans = [
        _mkspan("t1", "r", None, "rados_op", 0.0, 10_000,
                {"ops": "write", "client": "c9"}),
        _mkspan("t1", "o", "r", "osd_op", 0.001, 8_000,
                {"queue_wait_us": 1_500.0}),
        _mkspan("t1", "e", "o", "ec_encode", 0.002, 3_000),
        _mkspan("t1", "d", "e", "offload_batch", 0.003, 2_000,
                {"pool_wait_us": 200.0, "h2d_submit_us": 400.0,
                 "launch_us": 1_000.0, "result_wait_us": 300.0,
                 "resume_us": 90.0, "scatter_us": 10.0}),
        _mkspan("t1", "c", "o", "store_commit", 0.004, 2_500),
    ]
    cp = critpath.critical_path(spans)
    assert cp["total_us"] == 10_000
    assert cp["op_class"] == "write" and cp["client"] == "c9"
    st = cp["stages"]
    assert sum(st.values()) == pytest.approx(cp["total_us"], abs=0.01)
    assert st["queue_wait"] == 1_500
    assert st["h2d"] == 400 and st["kernel"] == 1_000 and st["d2h"] == 300
    # encode = EC span minus the nested device time
    assert st["encode"] == pytest.approx(3_000 - 1_700, abs=0.01)
    assert st["commit"] == 2_500
    assert cp["top_stage"] == "commit"

    # unprofiled dispatch: the whole device span counts as kernel, and
    # over-claiming stages scale DOWN to keep the sum exact
    spans2 = [
        _mkspan("t2", "r", None, "rados_op", 0.0, 1_000, {"ops": "read"}),
        _mkspan("t2", "d", "r", "tpu_decode_dispatch", 0.001, 900),
        _mkspan("t2", "c", "r", "store_commit", 0.002, 400),
    ]
    cp2 = critpath.critical_path(spans2)
    assert sum(cp2["stages"].values()) == pytest.approx(1_000, abs=0.01)
    assert cp2["stages"]["kernel"] > 0 and cp2["stages"]["other"] >= 0


def test_waterfall_rows_and_depths():
    spans = [
        _mkspan("t1", "r", None, "rados_op", 100.0, 5_000),
        _mkspan("t1", "a", "r", "osd_op", 100.001, 3_000),
        _mkspan("t1", "b", "a", "store_commit", 100.002, 1_000),
    ]
    rows = critpath.waterfall(spans)
    assert [r["depth"] for r in rows] == [0, 1, 2]
    assert rows[0]["offset_us"] == 0.0
    assert rows[1]["offset_us"] == pytest.approx(1_000, rel=0.01)
    assert all(r["on_critical_path"] for r in rows)


# ---------------------------------------------------------------------------
# mgr TraceIndex: ingest / dedup / links / settle (tentpole b + c)
# ---------------------------------------------------------------------------

def _envelope(pid, boot, spans, nxt=None):
    return {"pid": pid, "boot": boot, "spans": spans,
            "next": nxt if nxt is not None else
            max((s["seq"] for s in spans), default=0)}


def test_trace_index_ingest_dedup_and_restart():
    tix = TraceIndex()
    s1 = _mkspan("tA", "s1", None, "osd_op", 1.0, 500, seq=1)
    s2 = _mkspan("tA", "s2", "s1", "store_commit", 1.1, 100, seq=2)
    assert tix.ingest(_envelope(10, "a", [s1, s2])) == 2
    # co-located daemon replays the same collector: deduped by seq
    assert tix.ingest(_envelope(10, "a", [s1, s2])) == 0
    # a RESTARTED process reusing the pid gets a fresh boot token: its
    # seq=1 is a different span, not a replay
    s1b = _mkspan("tA", "s9", "s1", "pg_op", 1.2, 50, seq=1)
    assert tix.ingest(_envelope(10, "b", [s1b])) == 1
    got = tix.get("tA")
    assert got["num_spans"] == 3
    assert sorted(got["processes"]) == ["10:a", "10:b"]


def test_trace_index_links_pull_batch_span_into_rider():
    """An offload batch span owned by trace tB but LINKING rider tA is
    assembled into tA's waterfall (and critical path input)."""
    tix = TraceIndex()
    rider = _mkspan("tA", "r", None, "rados_op", 1.0, 900,
                    {"ops": "write"}, seq=1)
    batch = _mkspan("tB", "b", None, "offload_batch", 1.0005, 300,
                    seq=2, links=[{"trace_id": "tA", "span_id": "r"}])
    tix.ingest(_envelope(11, "x", [rider, batch]))
    got = tix.get("tA")
    assert got["num_spans"] == 2
    assert {r["name"] for r in got["waterfall"]} == \
        {"rados_op", "offload_batch"}
    # reverse index exists, and tB's own assembly is untouched
    assert tix.get("tB")["num_spans"] == 1


def test_trace_index_settles_and_banks_once():
    tix = TraceIndex()
    tix.SETTLE_S = 0.0
    spans = [_mkspan("tC", "r", None, "rados_op", 1.0, 2_000,
                     {"ops": "write", "client": "c1"}, seq=1),
             _mkspan("tC", "c", "r", "store_commit", 1.0005, 900, seq=2)]
    tix.ingest(_envelope(12, "z", spans))
    assert tix.settle() == 1
    assert tix.settle() == 0            # banked exactly once
    assert tix.banked_traces == 1
    h = tix.class_hists[("write", "commit")]
    assert h["count"] == 1 and h["sum"] == pytest.approx(900)
    assert tix.client_hists[("c1", "commit")]["count"] == 1
    ex = tix.exemplars["write"]
    assert ex["trace_id"] == "tC" and ex["total_us"] == 2_000
    # a straggler refines `trace get` but never re-banks
    tix.ingest(_envelope(12, "z", [
        _mkspan("tC", "l", "r", "ms_send", 1.0001, 100, seq=3)]))
    assert tix.get("tC")["num_spans"] == 3
    assert tix.settle() == 0 and tix.banked_traces == 1

    # slowest: sorted by total, filterable by class
    tix.ingest(_envelope(12, "z", [
        _mkspan("tD", "r2", None, "rados_op", 2.0, 9_000,
                {"ops": "read"}, seq=4)]))
    sl = tix.slowest(5)
    assert [t["trace_id"] for t in sl][:2] == ["tD", "tC"]
    assert [t["trace_id"] for t in tix.slowest(5, "write")] == ["tC"]


def test_trace_index_bounded_by_mgr_max_traces():
    tix = TraceIndex()
    tix.configure(max_traces=8)
    for i in range(30):
        tix.ingest(_envelope(13, "q", [
            _mkspan(f"t{i}", f"s{i}", None, "osd_op", float(i), 10,
                    seq=i + 1)]))
    assert len(tix.traces) == 8
    assert tix.get("t0") is None and tix.get("t29") is not None


def test_exporter_renders_trace_families_and_exemplars():
    tix = TraceIndex()
    tix.SETTLE_S = 0.0
    tix.ingest(_envelope(14, "w", [
        _mkspan("tE", "r", None, "rados_op", 1.0, 4_000,
                {"ops": "write", "client": "c2"}, seq=1),
        _mkspan("tE", "c", "r", "store_commit", 1.001, 1_500, seq=2)]))
    idx = DaemonStateIndex()
    idx.traces = tix
    text = render_metrics(index=idx)
    assert "# TYPE ceph_trace_critical_path_us histogram" in text
    assert 'op_class="write",stage="commit"' in text
    assert "# TYPE ceph_trace_client_critical_path_us histogram" in text
    assert 'ceph_client="c2"' in text
    # exemplar: its own gauge series naming the trace, NOT a bucket
    # suffix — bucket lines stay `name{labels} int`-parseable
    assert ('ceph_op_total_us_exemplar{op_class="write",'
            'trace_id="tE",top_stage="commit"}') in text
    for ln in text.splitlines():
        if "_bucket" in ln:
            int(ln.rsplit(" ", 1)[1])
    # cumulative within one family+label set
    lines = [ln for ln in text.splitlines()
             if ln.startswith("ceph_trace_critical_path_us_bucket"
                              '{op_class="write",stage="commit"')]
    vals = [int(ln.rsplit(" ", 1)[1]) for ln in lines]
    assert vals == sorted(vals) and vals[-1] == 1


def test_mgr_trace_commands_surface(tmp_path):
    """`trace get` / `trace slowest` on a non-started mgr: the local
    process collector is folded in, unknown ids error with index
    status attached."""
    mgr = MgrDaemon([("127.0.0.1", 1)], modules=[], exporter_port=None,
                    admin_socket_path=str(tmp_path / "mgr.asok"))
    mgr.daemon_index.traces.SETTLE_S = 0.0
    tracer.set_sampling(rate=1.0)
    with tracer.span("rados_op", "client.7") as sp:
        sp.set_tag("ops", "write")
        with tracer.span("store_commit"):
            time.sleep(0.001)
    tid = _collected()[0]["trace_id"]
    got = mgr.trace_get(tid)
    assert got["num_spans"] == 2 and len(got["processes"]) == 1
    cp = got["critical_path"]
    assert sum(cp["stages"].values()) == pytest.approx(cp["total_us"],
                                                       abs=0.01)
    sl = mgr.trace_slowest(5)
    assert any(t["trace_id"] == tid for t in sl["traces"])
    missing = mgr.trace_get("ffffffffffffffff")
    assert "error" in missing and "index" in missing


# ---------------------------------------------------------------------------
# acceptance: cross-process assembly on a reactor_procs=2 cluster
# ---------------------------------------------------------------------------

def test_cluster_assembly_across_processes(monkeypatch):
    """The ISSUE's acceptance drill: EC writes on a process-backed
    (reactor_procs=2) cluster with head sampling at 1% + tail
    retention are captured, `trace get` returns ONE assembled
    waterfall with spans from >= 2 OS processes, the critical-path
    stage sum equals op_total within the `other` residual, and the
    exporter ties an exemplar trace_id to the latency families."""
    monkeypatch.setattr(MgrClient, "REPORT_PERIOD", 0.2)
    monkeypatch.setattr(MgrDaemon, "TICK_INTERVAL", 0.2)
    monkeypatch.setattr(MgrDaemon, "REPORT_PERIOD", 0.2)
    monkeypatch.setattr(TraceIndex, "SETTLE_S", 0.2)

    async def body():
        import os

        from ceph_tpu.tools.cluster_boot import ephemeral_cluster
        async with ephemeral_cluster(
                3, prefix="trace2-",
                reactor_procs=2) as (client, osds, mon):
            mgr = MgrDaemon(list(mon.monmap.mons.values()),
                            exporter_port=None)
            await mgr.start()
            try:
                await client.command({
                    "prefix": "osd erasure-code-profile set",
                    "name": "t2prof",
                    "profile": {"plugin": "jerasure", "k": "2",
                                "m": "1", "technique": "reed_sol_van"}})
                await client.pool_create("t2", pg_num=4,
                                         pool_type="erasure",
                                         erasure_code_profile="t2prof")
                io = client.ioctx("t2")
                await io.write_full("warm", b"w" * 8192)

                # arm tracing v2 everywhere: 1% head sampling + a tail
                # threshold every real EC write (sockets + fork
                # boundaries) clears — the "deliberately slowed" op
                pool = osds[0].pool
                await pool.config_set("tracer_sample_rate", 0.01)
                await pool.config_set("tracer_tail_slow_ms", 0.5)
                tracer.set_sampling(rate=0.01, tail_slow_ms=0.5)

                for i in range(4):
                    await io.write_full(f"slow-{i}", b"s" * 65536)

                # the workers' MgrClients ship promoted spans on their
                # report legs; the mgr assembles by trace_id
                deadline = asyncio.get_running_loop().time() + 45
                assembled = None
                while assembled is None:
                    sl = mgr.trace_slowest(10, "write_full")["traces"]
                    for t in sl:
                        got = mgr.trace_get(t["trace_id"])
                        if "error" not in got and \
                                len(got["processes"]) >= 2:
                            assembled = got
                            break
                    if assembled is None:
                        assert asyncio.get_running_loop().time() < \
                            deadline, \
                            f"no multi-process trace assembled: {sl}"
                        await asyncio.sleep(0.3)

                # one waterfall spanning >= 2 OS processes, the parent
                # (client) among them
                assert assembled["num_spans"] >= 3
                pids = {p.split(":", 1)[0]
                        for p in assembled["processes"]}
                assert len(pids) >= 2
                assert str(os.getpid()) in pids
                names = {r["name"] for r in assembled["waterfall"]}
                assert "rados_op" in names          # client side
                assert names & {"osd_op", "pg_op", "ms_dispatch",
                                "ec_write", "store_commit"}  # osd side

                # critical-path invariant on the REAL assembled trace
                cp = assembled["critical_path"]
                assert cp["op_class"] == "write_full"
                assert sum(cp["stages"].values()) == \
                    pytest.approx(cp["total_us"], abs=0.1)
                assert cp["stages"]["other"] >= 0

                # exporter: exemplar series naming a settled trace
                deadline = asyncio.get_running_loop().time() + 20
                while True:
                    text = render_metrics(index=mgr.daemon_index)
                    if "ceph_op_total_us_exemplar" in text and \
                            "ceph_trace_critical_path_us" in text:
                        break
                    assert asyncio.get_running_loop().time() < \
                        deadline, "trace families never exported"
                    await asyncio.sleep(0.3)
                exemplar = next(
                    ln for ln in text.splitlines()
                    if ln.startswith("ceph_op_total_us_exemplar")
                    and 'op_class="write_full"' in ln)
                tid = exemplar.split('trace_id="', 1)[1].split('"')[0]
                assert "error" not in mgr.trace_get(tid)
            finally:
                tracer.set_sampling(rate=0.0, tail_slow_ms=0.0)
                await mgr.stop()
    run(body(), timeout=180)
