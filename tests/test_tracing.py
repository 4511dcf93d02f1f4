"""End-to-end op tracing (the src/common/tracer.cc Jaeger analog):
span propagation client -> messenger -> PG -> EC encode -> objectstore
over real sockets, admin-socket `trace dump`, prometheus histogram
export, disabled-mode zero-overhead, the mon cluster-log channel, and
the messenger shutdown task-leak regression."""
from __future__ import annotations

import asyncio

import pytest

from ceph_tpu.mgr.exporter import render_metrics
from ceph_tpu.msg.messenger import Messenger, Policy
from ceph_tpu.msg.messages import MPing
from ceph_tpu.utils import tracer
from ceph_tpu.utils.admin_socket import AdminSocket, admin_command

from tests.test_cluster import ClusterHarness, fast_timers, run  # noqa: F401


@pytest.fixture(autouse=True)
def clean_tracer():
    """Every test starts and ends with tracing off and the collector
    empty (the collector is process-wide)."""
    tracer.disable()
    tracer.set_sampling(rate=0.0, tail_slow_ms=0.0)
    tracer.reset()
    yield
    tracer.disable()
    tracer.set_sampling(rate=0.0, tail_slow_ms=0.0)
    tracer.reset()


def _span_index(trace: dict) -> dict[str, dict]:
    return {s["span_id"]: s for s in trace["spans"]}


def _chain_reaches_root(span: dict, by_id: dict[str, dict]) -> bool:
    seen = set()
    while span["parent_id"] is not None:
        if span["span_id"] in seen:
            return False
        seen.add(span["span_id"])
        span = by_id.get(span["parent_id"])
        if span is None:
            return False
    return span["name"] == "rados_op"


def test_ec_write_produces_one_connected_trace(tmp_path):
    """A single rados put to an EC pool over real sockets yields ONE
    trace whose spans cover client, messenger (both ends), PG op
    processing, EC encode (with bytes + k/m tags), and objectstore
    commit — and the admin socket dumps it."""
    async def body():
        c = ClusterHarness(tmp_path)
        try:
            await c.start()
            cl = await c.client()
            await cl.command({"prefix": "osd erasure-code-profile set",
                              "name": "jprof",
                              "profile": {"plugin": "jerasure", "k": "2",
                                          "m": "1",
                                          "technique": "reed_sol_van"}})
            await cl.pool_create("ecpool", pg_num=4, pool_type="erasure",
                                 erasure_code_profile="jprof")
            io = cl.ioctx("ecpool")
            tracer.enable()
            tracer.collector().reset()
            await io.write_full("traced-obj", b"t" * 9000)
            tracer.disable()

            dump = tracer.dump()
            puts = [t for t in dump["traces"]
                    if any(s["name"] == "rados_op"
                           and s["tags"].get("oid") == "traced-obj"
                           for s in t["spans"])]
            assert len(puts) == 1, [t["root"] for t in dump["traces"]]
            trace = puts[0]
            names = {s["name"] for s in trace["spans"]}
            # client + messenger both ends + PG + EC write path + store
            assert {"rados_op", "ms_send", "ms_dispatch", "osd_op",
                    "pg_op", "ec_write", "ec_encode",
                    "store_commit"} <= names, sorted(names)
            # messenger spans exist on BOTH ends: the client->osd hop and
            # the primary->shard sub-op hops, each dispatched osd-side
            services = {s["service"] for s in trace["spans"]
                        if s["name"] == "ms_dispatch"}
            assert any(svc.startswith("osd.") for svc in services)
            # clients carry per-instance identities (client.<id>) since
            # the per-client accounting PR; the span service names one
            assert any(s["service"].startswith("client")
                       for s in trace["spans"] if s["name"] == "ms_send")
            # EC encode span carries bytes + geometry tags
            enc = next(s for s in trace["spans"]
                       if s["name"] == "ec_encode")
            assert enc["tags"]["k"] == 2 and enc["tags"]["m"] == 1
            assert enc["tags"]["bytes"] >= 9000
            # every span chains back to the client root: one CONNECTED
            # trace, not islands sharing a trace id
            by_id = _span_index(trace)
            for s in trace["spans"]:
                assert _chain_reaches_root(s, by_id), s["name"]

            # admin socket surface: trace dump over a real unix socket
            asok = AdminSocket(str(tmp_path / "asok"))
            asok.start()
            try:
                got = await asyncio.to_thread(
                    admin_command, str(tmp_path / "asok"), "trace dump")
                tids = [t["trace_id"] for t in got["result"]["traces"]]
                assert trace["trace_id"] in tids
                got = await asyncio.to_thread(
                    admin_command, str(tmp_path / "asok"), "trace reset")
                assert got["result"]["cleared"] > 0
            finally:
                asok.stop()

            # the op landed in the histograms and exports as cumulative
            # prometheus series
            text = render_metrics()
            for metric in ("ceph_op_total_us", "ceph_op_queue_wait_us",
                           "ceph_ec_encode_us", "ceph_store_commit_us"):
                assert f"# TYPE {metric} histogram" in text, metric
                assert f"{metric}_bucket" in text
                assert 'le="+Inf"' in text
                assert f"{metric}_sum" in text
                assert f"{metric}_count" in text
            # cumulative: +Inf count equals _count for one daemon line
            lines = [ln for ln in text.splitlines()
                     if ln.startswith('ceph_ec_encode_us_bucket'
                                      '{ceph_daemon="osd.0"')]
            if lines:
                vals = [int(ln.rsplit(" ", 1)[1]) for ln in lines]
                assert vals == sorted(vals)

            # historic ops carry the trace id (on whichever osd was the
            # write's primary)
            assert any(
                op.get("trace_id") == trace["trace_id"]
                for osd in c.osds.values()
                for op in osd.optracker.dump_historic_ops()["ops"])
        finally:
            await c.stop()
    run(body())


def _full_round_ms() -> float:
    import gc
    import time
    best = None
    for _ in range(5):
        t0 = time.perf_counter()
        gc.collect()
        took = (time.perf_counter() - t0) * 1e3
        best = took if best is None else min(best, took)
    return best


def test_a_full_collection_does_not_walk_the_spans_the_collector_holds():
    """100,000 finished spans held (the benchmark's harness holds every
    span of a window): what the collector keeps of each is a tuple of
    atoms, which the garbage collector stops tracking at the first round
    that meets it. So the objects a full round visits grow by under one
    for fifty spans held (a `Span` object a span, as before PR 55: one
    for one, 0.14-0.33 us a span and round), and a full round with them
    held stays under twice one with none (and 5 ms; 1.1 times alone
    on this machine's CPU, where the old form read 1.6)."""
    import gc
    tracer.reset()
    none_held = _full_round_ms()
    visited = len(gc.get_objects())
    tracer.enable(max_spans=200_000)
    try:
        with tracer.span("rados_op", "client"):
            for i in range(100_000):
                with tracer.span("osd_op", "osd.0") as sp:
                    sp.set_tag("bytes", i)
                    sp.set_tag("type", "MOSDOp")
        assert len(tracer.collector()) == 100_001
        held = _full_round_ms()
        assert len(gc.get_objects()) - visited < 2_000
        records = list(tracer.collector()._spans)
        assert not any(gc.is_tracked(r) or gc.is_tracked(r[7])
                       for r in records[::1000])
        last = tracer.collector().spans()[-2]
        assert (last["name"], last["service"], last["tags"]) == \
            ("osd_op", "osd.0", {"bytes": 99_999, "type": "MOSDOp"})
        assert last["parent_id"] == tracer.collector().spans()[-1]["span_id"]
    finally:
        tracer.disable()
        tracer.reset()
    assert held < 2.0 * none_held + 5.0, (held, none_held)


def test_tracing_disabled_is_a_noop(tmp_path):
    """With tracing off, trace calls are no-ops: span() hands back one
    shared null context manager (no span objects allocated) and nothing
    is retained by the collector, even across a real cluster write."""
    assert not tracer.enabled()
    assert tracer.span("x") is tracer._NOOP
    assert tracer.span("y", "svc") is tracer._NOOP
    with tracer.span("z") as sp:
        assert sp is None
    assert tracer.current_context() is None
    assert tracer.start_span("w") is None

    async def body():
        c = ClusterHarness(tmp_path, n_osds=3)
        try:
            await c.start()
            cl = await c.client()
            await cl.pool_create("rbd", pg_num=4, size=3)
            io = cl.ioctx("rbd")
            await io.write_full("o", b"x" * 1000)
            assert await io.read("o") == b"x" * 1000
        finally:
            await c.stop()
    run(body())
    assert len(tracer.collector()) == 0
    assert tracer.dump()["traces"] == []


@pytest.mark.parametrize("make", [
    lambda: tracer.span("osd_op"), lambda: tracer.span("x", "svc", None),
    lambda: tracer.section("msgr.codec"), lambda: tracer.section("osd.ec"),
    lambda: tracer.span_sampled_only("pg_op"),
], ids=["span", "span_args", "section", "section_osd", "sampled_only"])
def test_tracing_off_hands_out_the_one_shared_noop(make):
    """Off is off for the self-account too: every CM is the one `_NOOP`,
    `start_span` is None, and no hook of the account is held."""
    assert not tracer.active()
    assert make() is tracer._NOOP
    assert tracer.start_span("ms_send") is None
    assert tracer._acct_span is tracer._acct_section is None
    assert tracer._acct_open is None
    before = tracer._timed_k
    with make() as got:
        assert got is None
    assert tracer._timed_k == before


def test_tracer_config_hot_toggle():
    """`config set tracer_enabled true` flips collection live (observer
    hot reload), and tracer_max_spans bounds the collector."""
    from ceph_tpu.utils.config import Config
    cfg = Config()
    tracer.register_config(cfg)
    assert not tracer.enabled()
    cfg.set("tracer_enabled", True)
    assert tracer.enabled()
    with tracer.span("live"):
        pass
    assert len(tracer.collector()) == 1
    cfg.set("tracer_max_spans", 16)
    for i in range(40):
        with tracer.span(f"s{i}"):
            pass
    assert len(tracer.collector()) == 16
    assert tracer.collector().dropped > 0
    cfg.set("tracer_enabled", False)
    assert not tracer.enabled()


def test_messenger_shutdown_reaps_dispatch_tasks():
    """Regression for the BENCH_r05 `Task was destroyed but it is
    pending! Connection._dispatch_loop` leak: after sessions end (clean
    shutdown AND lossy reset), no dispatch-loop task survives."""
    async def body():
        def dispatch_tasks():
            return [t for t in asyncio.all_tasks()
                    if not t.done() and "_dispatch_loop" in repr(t)]

        srv = Messenger("srv")
        await srv.bind("127.0.0.1", 0)
        cli = Messenger("cli")
        conn = await cli.connect(srv.my_addr, Policy.lossy_client())
        conn.send_message(MPing({"stamp": 1.0}))
        await asyncio.sleep(0.2)
        assert dispatch_tasks()            # sessions alive -> loops alive

        # lossy reset path: the server dies, the client session resets
        # and its _run returns without close() ever being called
        await srv.shutdown()
        deadline = asyncio.get_running_loop().time() + 5
        while dispatch_tasks():
            if asyncio.get_running_loop().time() > deadline:
                raise AssertionError(
                    f"leaked dispatch tasks: {dispatch_tasks()}")
            await asyncio.sleep(0.05)
        await cli.shutdown()
        assert not dispatch_tasks()
    run(body())


def test_mon_cluster_log_channel(tmp_path):
    """WARN+ daemon events land in the mon ring and `log last` returns
    them; an osd failure logs both the reporter's and the mon's line."""
    async def body():
        c = ClusterHarness(tmp_path, n_osds=3)
        try:
            await c.start()
            cl = await c.client()
            # a pool gives the osds PGs (and therefore heartbeat peers,
            # without which nobody reports the kill below)
            await cl.pool_create("rbd", pg_num=4, size=3)
            # direct daemon -> mon line
            await cl.monc.send_log("WRN", "client.test", "hello cluster log")
            # sub-WARN levels never travel
            await cl.monc.send_log("INF", "client.test", "debug chatter")
            deadline = asyncio.get_running_loop().time() + 10
            while True:
                out = await cl.command({"prefix": "log last", "num": 50})
                msgs = [e["message"] for e in out["lines"]]
                if "hello cluster log" in msgs:
                    break
                if asyncio.get_running_loop().time() > deadline:
                    raise AssertionError(f"log line never landed: {msgs}")
                await asyncio.sleep(0.1)
            assert "debug chatter" not in msgs
            entry = next(e for e in out["lines"]
                         if e["message"] == "hello cluster log")
            assert entry["level"] == "WRN" and entry["who"] == "client.test"

            # real health event: kill an osd; heartbeat reporters and the
            # mon's mark-down both log WARN lines
            await c.kill_osd(2)
            await c.wait_osd_down(2)
            deadline = asyncio.get_running_loop().time() + 15
            while True:
                out = await cl.command({"prefix": "log last", "num": 100})
                msgs = [e["message"] for e in out["lines"]]
                if any("osd.2 marked down" in m for m in msgs) and \
                        any("no heartbeat reply from osd.2" in m
                            for m in msgs):
                    break
                if asyncio.get_running_loop().time() > deadline:
                    raise AssertionError(f"failure never logged: {msgs}")
                await asyncio.sleep(0.2)
            # level filter
            out = await cl.command({"prefix": "log last", "num": 100,
                                    "level": "WRN"})
            assert all(e["level"] == "WRN" for e in out["lines"])
        finally:
            await c.stop()
    run(body())
