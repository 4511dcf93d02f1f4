"""Offload-service tests: dynamic batching for the in-situ EC data path.

Covers the ISSUE-3 acceptance surface: concurrent submits coalescing
into one device batch (including across two PGs of a live cluster),
flush-on-bytes vs linger-deadline semantics, admission backpressure,
the device-failure circuit breaker (host fallback bit-identical, no
lost ops, health metric trips then clears, mgr digests it into
TPU_OFFLOAD_DEGRADED), and the admin-socket/config surfaces.
"""
from __future__ import annotations

import asyncio
import time

import numpy as np
import pytest

from ceph_tpu import offload
from ceph_tpu.ec import registry
from ceph_tpu.ec.plugin_tpu import ErasureCodeTpu
from ceph_tpu.msg.messenger import Connection
from ceph_tpu.mon.paxos import Paxos
from ceph_tpu.offload import service as offload_service
from ceph_tpu.osd import ec_util
from ceph_tpu.osd.daemon import OSD
from ceph_tpu.utils.config import Config

from tests.test_cluster import ClusterHarness, run


@pytest.fixture(autouse=True)
def fast_timers(monkeypatch):
    monkeypatch.setattr(Paxos, "ELECTION_TIMEOUT", 0.15)
    monkeypatch.setattr(Paxos, "LEASE_INTERVAL", 0.2)
    monkeypatch.setattr(Paxos, "LEASE_TIMEOUT", 1.0)
    monkeypatch.setattr(Paxos, "ACCEPT_TIMEOUT", 0.8)
    monkeypatch.setattr(Connection, "KEEPALIVE_INTERVAL", 0.3)
    monkeypatch.setattr(Connection, "KEEPALIVE_TIMEOUT", 1.5)
    monkeypatch.setattr(Connection, "PARK_TIMEOUT", 2.0)
    monkeypatch.setattr(OSD, "HB_INTERVAL", 0.25)
    monkeypatch.setattr(OSD, "HB_GRACE", 1.2)


def _impl(k=4, m=2):
    return registry.factory("tpu", {"k": str(k), "m": str(m)})


# ---------------------------------------------------------------------------
# service-level behavior
# ---------------------------------------------------------------------------

def test_concurrent_submits_coalesce_into_one_batch():
    async def body():
        impl = _impl()
        sinfo = ec_util.StripeInfo(4, 4 * 1024)
        svc = offload.get_service()
        svc.linger_ms = 25.0
        base = dict(svc.stats)
        data = bytes(range(256)) * 64            # 4 stripes
        ref = ec_util.encode(sinfo, impl, data)
        outs = await asyncio.gather(*[
            ec_util.encode_async(sinfo, impl, data, service=svc)
            for _ in range(6)])
        for out in outs:
            assert out == ref                    # batching changes nothing
        d = {k: svc.stats[k] - base[k] for k in base}
        assert d["jobs"] == 6
        assert d["batches"] == 1                 # ONE device dispatch
        assert d["coalesced_ops"] == 5
        await svc.drain()
    run(body(), timeout=60)


def test_flush_on_max_batch_bytes_beats_linger():
    async def body():
        impl = _impl()
        sinfo = ec_util.StripeInfo(4, 4 * 1024)
        svc = offload.get_service()
        svc.linger_ms = 60_000.0                 # linger would hang the test
        data = bytes(4 * 1024 * 2)               # 8 KiB -> 2 stripes
        svc.max_batch_bytes = 2 * len(data)      # two jobs fill a batch
        try:
            outs = await asyncio.wait_for(asyncio.gather(
                ec_util.encode_async(sinfo, impl, data, service=svc),
                ec_util.encode_async(sinfo, impl, data, service=svc)), 20)
        finally:
            svc.linger_ms = 2.0
        ref = ec_util.encode(sinfo, impl, data)
        assert outs[0] == ref and outs[1] == ref
        await svc.drain()
    run(body(), timeout=60)


def test_lone_op_ships_at_linger_deadline():
    async def body():
        impl = _impl()
        sinfo = ec_util.StripeInfo(4, 4 * 1024)
        svc = offload.get_service()
        svc.linger_ms = 5.0
        data = bytes(4 * 1024)
        out = await asyncio.wait_for(
            ec_util.encode_async(sinfo, impl, data, service=svc), 20)
        assert out == ec_util.encode(sinfo, impl, data)
        await svc.drain()
    run(body(), timeout=60)


def test_backpressure_bounds_admitted_bytes():
    async def body():
        impl = _impl()
        sinfo = ec_util.StripeInfo(4, 4 * 1024)
        svc = offload.get_service()
        svc.linger_ms = 1.0
        data = bytes(4 * 1024 * 4)
        # budget of ~one job: admissions serialize, nothing is lost
        svc._throttle.reset_max(len(data) + 1)
        try:
            ref = ec_util.encode(sinfo, impl, data)
            outs = await asyncio.wait_for(asyncio.gather(*[
                ec_util.encode_async(sinfo, impl, data, service=svc)
                for _ in range(5)]), 30)
            assert all(o == ref for o in outs)
            assert svc._throttle.current == 0    # fully released
            # a job BIGGER than the whole budget admits alone (transient
            # overshoot) instead of starving behind smaller traffic
            svc._throttle.reset_max(len(data) // 2)
            big = await asyncio.wait_for(
                ec_util.encode_async(sinfo, impl, data, service=svc), 20)
            assert big == ref
            assert svc._throttle.current == 0
        finally:
            svc._throttle.reset_max(64 << 20)
        await svc.drain()
    run(body(), timeout=60)


def test_device_failure_falls_back_identical_then_breaker_clears():
    async def body():
        impl = _impl()
        sinfo = ec_util.StripeInfo(4, 4 * 1024)
        svc = offload.get_service()
        svc.linger_ms = 2.0
        svc.breaker_reset_s = 0.05
        data = bytes(range(256)) * 64
        ref = ec_util.encode(sinfo, impl, data)

        orig = impl.encode_stripes
        impl.encode_stripes = lambda s: (_ for _ in ()).throw(
            RuntimeError("injected device failure"))
        out = await ec_util.encode_async(sinfo, impl, data, service=svc)
        assert out == ref                        # host codec bit-identical
        hm = svc.health_metrics()
        assert hm["degraded"] and hm["breaker_trips"] >= 1
        assert "injected device failure" in hm["last_error"]
        # while degraded: still correct, still served, counted as fallback
        before = svc.stats["fallback_ops"]
        out2 = await ec_util.encode_async(sinfo, impl, data, service=svc)
        assert out2 == ref
        assert svc.stats["fallback_ops"] > before

        impl.encode_stripes = orig
        await asyncio.sleep(0.06)                # cooldown -> probe allowed
        out3 = await ec_util.encode_async(sinfo, impl, data, service=svc)
        assert out3 == ref
        assert not svc.degraded                  # metric cleared
        assert not svc.health_metrics()["degraded"]
        await svc.drain()
    run(body(), timeout=60)


def test_decode_jobs_bucket_by_erasure_pattern():
    async def body():
        impl = _impl()
        sinfo = ec_util.StripeInfo(4, 4 * 1024)
        svc = offload.get_service()
        svc.linger_ms = 25.0
        data = bytes(range(256)) * 64
        ref = ec_util.encode(sinfo, impl, data)
        base = dict(svc.stats)
        sub = {i: ref[i] for i in (0, 2, 3, 4)}          # shard 1 missing
        sub2 = {i: ref[i] for i in (0, 1, 3, 5)}         # shard 2 missing
        outs = await asyncio.gather(
            ec_util.decode_concat_async(sinfo, impl, sub, service=svc),
            ec_util.decode_concat_async(sinfo, impl, sub, service=svc),
            ec_util.decode_concat_async(sinfo, impl, sub2, service=svc))
        assert all(o == data for o in outs)
        d = {k: svc.stats[k] - base[k] for k in base}
        # the two same-pattern jobs share a batch; the third cannot
        assert d["jobs"] == 3 and d["batches"] == 2
        assert d["coalesced_ops"] == 1
        await svc.drain()
    run(body(), timeout=60)


def test_counters_and_tags_say_which_kind_and_which_flush_rule():
    """`enc_*` count what the `kind` tag of an `offload_batch` span
    marks (beside `dec_*` and `crc_*`), `flush_linger` / `flush_full`
    what its `flush` tag says (a `drain()` is neither rule: `asked`),
    and a rider's `offload_queue_wait` carries its batch's kind."""
    from ceph_tpu.utils import tracer

    async def body():
        impl = _impl(2, 1)
        stripes = np.arange(2 * 2 * 4096, dtype=np.uint8).reshape(2, 2, 4096)
        svc = offload.get_service()
        tracer.enable(max_spans=10_000)
        cursor = tracer.collector().last_seq()
        try:
            await asyncio.gather(*[svc.encode(impl, stripes)
                                   for _ in range(3)])       # the linger
            svc.max_batch_bytes = 2 * stripes.nbytes
            await asyncio.gather(*[svc.encode(impl, stripes)
                                   for _ in range(4)])       # two full
            lone = asyncio.ensure_future(svc.encode(impl, stripes))
            await asyncio.sleep(0)
            await svc.drain()                                # asked
            await lone
            await svc.decode(impl, (0, 2), (1,), stripes)
        finally:
            tracer.disable()
        spans = [s for s in tracer.collector().spans() if s["seq"] > cursor]
        batches = [s["tags"] for s in spans if s["name"] == "offload_batch"]
        waits = [s["tags"] for s in spans
                 if s["name"] == "offload_queue_wait"]
        assert [(t["kind"], t["flush"], t["ops"]) for t in batches] == [
            ("enc", "linger", 3), ("enc", "full", 2), ("enc", "full", 2),
            ("enc", "asked", 1), ("dec", "linger", 1)]
        assert [t["kind"] for t in waits] == ["enc"] * 8 + ["dec"]
        st = svc.stats
        assert (st["flush_linger"], st["flush_full"]) == (2, 2)
        assert (st["enc_jobs"], st["enc_batches"], st["enc_bytes"]) == \
            (8, 4, 8 * stripes.nbytes)
        assert (st["dec_jobs"], st["dec_batches"]) == (1, 1)
        assert (st["jobs"], st["batches"]) == (9, 5)
    run(body(), timeout=60)


def test_inline_bypass_when_disabled():
    async def body():
        impl = _impl()
        sinfo = ec_util.StripeInfo(4, 4 * 1024)
        svc = offload.get_service()
        data = bytes(4 * 1024)
        ref = ec_util.encode(sinfo, impl, data)
        # this loop's service only: it goes with the loop
        svc.apply_setting("ec_offload_enabled", False)
        base = dict(svc.stats)
        outs = await asyncio.gather(*[
            ec_util.encode_async(sinfo, impl, data, service=svc)
            for _ in range(3)])
        assert all(o == ref for o in outs)
        d = {k: svc.stats[k] - base[k] for k in base}
        assert d["batches"] == 3             # one dispatch per op
        assert d["coalesced_ops"] == 0
    run(body(), timeout=60)


# ---------------------------------------------------------------------------
# mesh fan-out: routing, sharding, per-device breakers, device rows
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("hot_count", [0, 3])
def test_device_affine_routing_with_least_busy_spillover(hot_count):
    """Same bucket key -> same device while it keeps up (compile-cache
    warmth); a backed-up preferred device spills to the least-busy one;
    with every device out of rotation the router yields None (host).
    `hot_count`: the same over the slots the next dispatch rebuilds
    after a hot `ec_offload_device_count` reset the service's one
    topology, the shards coming back bit-identical."""
    async def body():
        svc = offload.get_service()
        slots = svc._topology()
        assert len(slots) == 8               # conftest: 8 virtual devices
        if hot_count:
            impl = _impl()
            sinfo = ec_util.StripeInfo(4, 4 * 1024)
            data = bytes(range(256)) * 64
            ref = await ec_util.encode_async(sinfo, impl, data,
                                             service=svc)
            assert ref == ec_util.encode(sinfo, impl, data)
            topo, before = svc._topo, svc._topo.states
            assert [s.state for s in slots] == before
            svc.apply_setting("ec_offload_device_count", hot_count)
            assert svc._topo is topo         # reset, not replaced
            assert topo.states is None and svc._slots is None
            assert await ec_util.encode_async(sinfo, impl, data,
                                              service=svc) == ref
            slots = svc._slots               # rebuilt by that dispatch
            assert len(slots) == hot_count
            assert [s.state for s in slots] == topo.states
            assert not set(map(id, topo.states)) & set(map(id, before))
            await svc.drain()
        key = ("enc", b"matrix", 4096)
        pref = slots[hash(key) % len(slots)]
        for _ in range(4):                   # idle: affinity is stable
            assert svc._route(key) is pref
        pref.inflight = svc.device_spill_threshold
        try:
            spill = svc._route(key)
            assert spill is not pref
            assert spill.inflight == 0       # least busy won
        finally:
            pref.inflight = 0
        for s in slots:                      # all tripped -> host lane
            s.degraded = True
            s.degraded_since = time.monotonic()
        try:
            assert svc._route(key) is None
            assert svc.degraded              # TPU_OFFLOAD_DEGRADED state
        finally:
            for s in slots:
                s.degraded = False
        assert not svc.degraded
    run(body(), timeout=60)


def test_oversized_batch_stripe_shards_bit_identical():
    """A batch at device_shard_bytes fans across the whole mesh through
    sharded_encode_fn — output bit-identical to the single-device
    dispatch, counted as a mesh batch."""
    async def body():
        impl = _impl()
        sinfo = ec_util.StripeInfo(4, 4 * 1024)
        svc = offload.get_service()
        svc.linger_ms = 2.0
        prev = svc.device_shard_bytes
        svc.device_shard_bytes = 32 * 1024
        try:
            data = bytes(range(256)) * 16 * 64      # 256 KiB = 64 stripes
            ref = ec_util.encode(sinfo, impl, data)  # single-device path
            base = dict(svc.stats)
            out = await asyncio.wait_for(
                ec_util.encode_async(sinfo, impl, data, service=svc), 60)
            assert out == ref                        # bit-identical
            d = {k: svc.stats[k] - base[k] for k in base}
            assert d["mesh_batches"] == 1
            assert d["fallback_ops"] == 0
            st = svc.status()
            assert st["mesh"]["devices"] == 8
            assert st["mesh"]["shape"] == {"stripe": 8, "shard": 1}
        finally:
            svc.device_shard_bytes = prev
        await svc.drain()
    run(body(), timeout=120)


def test_per_device_breaker_isolates_one_chip(monkeypatch):
    """One chip failing fails its in-flight batch over to the next
    healthy chip: no host fallback, no service-wide degradation, only
    the victim leaves rotation."""
    async def body():
        impl = _impl()
        sinfo = ec_util.StripeInfo(4, 4 * 1024)
        svc = offload.get_service()
        svc.linger_ms = 2.0
        slots = svc._topology()
        data = bytes(range(256)) * 64
        ref = ec_util.encode(sinfo, impl, data)
        key = ("enc", impl.coding_matrix.tobytes(), sinfo.chunk_size)
        victim = slots[hash(key) % len(slots)]
        orig = svc._device_call

        async def boom(slot, fn, stacked, sp=None):
            if slot is victim:
                raise RuntimeError("chip down")
            return await orig(slot, fn, stacked, sp)
        monkeypatch.setattr(svc, "_device_call", boom)

        base = dict(svc.stats)
        out = await ec_util.encode_async(sinfo, impl, data, service=svc)
        assert out == ref
        d = {k: svc.stats[k] - base[k] for k in base}
        assert victim.degraded                   # victim out of rotation
        assert not svc.degraded                  # service still healthy
        assert d["breaker_trips"] == 1
        assert d["device_failovers"] >= 1        # batch failed over
        assert d["fallback_ops"] == 0            # never reached host
        hm = svc.health_metrics()
        assert not hm["degraded"] and hm["devices_out"] == 1
        # follow-up batches route around the victim without new trips
        base2 = dict(svc.stats)
        out2 = await ec_util.encode_async(sinfo, impl, data, service=svc)
        assert out2 == ref
        assert svc.stats["breaker_trips"] == base2["breaker_trips"]
        assert svc.stats["fallback_ops"] == base2["fallback_ops"]
        await svc.drain()
    run(body(), timeout=120)


def test_device_stats_and_exporter_rows_for_every_mesh_device(monkeypatch):
    """Concurrent distinct-bucket batches under load rotate over ALL
    mesh devices (spill threshold 1), and each device's stats render as
    a ceph_device-labeled exporter row."""
    from ceph_tpu.mgr.daemon import DaemonStateIndex
    from ceph_tpu.mgr.exporter import render_metrics

    async def body():
        impl = _impl()
        svc = offload.get_service()
        slots = svc._topology()
        svc.linger_ms = 1.0
        prev_spill, prev_batch = svc.device_spill_threshold, \
            svc.max_batch_bytes
        svc.device_spill_threshold = 1
        svc.max_batch_bytes = 4096           # every submit flushes

        orig = svc._device_call

        async def slow(*args):
            await asyncio.sleep(0.05)        # keep slots busy to rotate
            return await orig(*args)
        monkeypatch.setattr(svc, "_device_call", slow)
        try:
            # 16 distinct bucket keys (one per chunk size) in flight at
            # once: with spill threshold 1 every new batch lands on an
            # idle slot while one exists
            jobs = []
            for i in range(1, 17):
                sinfo = ec_util.StripeInfo(4, 4 * 1024 * i)
                data = bytes(4 * 1024 * i)
                jobs.append(ec_util.encode_async(sinfo, impl, data,
                                                 service=svc))
            await asyncio.wait_for(asyncio.gather(*jobs), 60)
        finally:
            svc.device_spill_threshold = prev_spill
            svc.max_batch_bytes = prev_batch
        seen = set(svc.device_snapshot())
        assert {s.label for s in slots} <= seen
        # report path: one ceph_device row per mesh device
        index = DaemonStateIndex()
        index.report({"daemon_name": "osd.9", "service": "osd",
                      "device_metrics": svc.device_metrics()})
        text = render_metrics(None, index=index)
        for s in slots:
            assert (f'ceph_offload_device_batches{{ceph_daemon="osd.9",'
                    f'ceph_device="{s.label}"}}') in text
        await svc.drain()
    run(body(), timeout=120)


# ---------------------------------------------------------------------------
# cluster-level behavior (real daemons, real sockets)
# ---------------------------------------------------------------------------

async def _ec_tpu_cluster(harness, k=2, m=1, pg_num=8):
    await harness.start()
    client = await harness.client()
    await client.command({
        "prefix": "osd erasure-code-profile set", "name": "offprof",
        "profile": {"plugin": "tpu", "k": str(k), "m": str(m)}})
    await client.pool_create("offpool", pg_num=pg_num,
                             pool_type="erasure",
                             erasure_code_profile="offprof")
    return client, client.ioctx("offpool")


def test_cross_pg_writes_share_one_device_batch(tmp_path, monkeypatch):
    """Two concurrent writes to objects in DIFFERENT PGs coalesce into
    one encode_stripes device dispatch (the cross-PG acceptance case)."""
    shapes: list[int] = []
    orig = ErasureCodeTpu.encode_stripes

    def spy(self, data):
        shapes.append(int(data.shape[0]))
        return orig(self, data)
    monkeypatch.setattr(ErasureCodeTpu, "encode_stripes", spy)

    async def body():
        harness = ClusterHarness(tmp_path, n_osds=3)
        client, io = await _ec_tpu_cluster(harness)
        try:
            svc = offload.get_service()
            svc.linger_ms = 300.0                # generous overlap window
            osd = next(iter(harness.osds.values()))
            # two objects in two different PGs, one stripe each
            names, seen = [], set()
            for i in range(64):
                pg = osd.osdmap.object_to_pg("offpool", f"x{i}")
                if pg not in seen:
                    seen.add(pg)
                    names.append(f"x{i}")
                if len(names) == 2:
                    break
            assert len(names) == 2
            stripe = 2 * 4096
            payloads = {n: bytes([i]) * stripe
                        for i, n in enumerate(names)}
            base = dict(svc.stats)
            await asyncio.gather(*[io.write_full(n, payloads[n])
                                   for n in names])
            svc.linger_ms = 2.0
            d = {k2: svc.stats[k2] - base[k2] for k2 in base}
            # one device batch carried both PGs' single-stripe encodes
            assert max(shapes) >= 2, shapes
            assert d["coalesced_ops"] >= 1
            for n in names:                      # nothing lost
                assert await io.read(n) == payloads[n]
        finally:
            svc.linger_ms = 2.0
            await harness.stop()
    run(body(), timeout=120)


def test_cluster_device_failure_fallback_no_lost_ops(tmp_path,
                                                     monkeypatch):
    """Injected device-codec failure mid-cluster: every write is served
    by the host fallback (identical data on read-back), the daemon
    health metric trips, and it clears after the breaker cooldown."""
    async def body():
        harness = ClusterHarness(tmp_path, n_osds=3)
        client, io = await _ec_tpu_cluster(harness)
        try:
            svc = offload.get_service()
            svc.breaker_reset_s = 0.05
            osd = next(iter(harness.osds.values()))

            def boom(self, data):
                raise RuntimeError("injected device failure")
            orig = ErasureCodeTpu.encode_stripes
            monkeypatch.setattr(ErasureCodeTpu, "encode_stripes", boom)
            payloads = {f"f{i}": bytes([i]) * (2 * 4096 * 2)
                        for i in range(8)}
            await asyncio.gather(*[io.write_full(n, p)
                                   for n, p in payloads.items()])
            assert svc.degraded
            hm = osd._mgr_health_metrics()["offload"]
            assert hm["degraded"] and hm["fallback_ops"] >= 1
            # no lost ops: everything written during degradation reads
            # back intact (host codec produced identical chunks)
            for n, p in payloads.items():
                assert await io.read(n) == p

            monkeypatch.setattr(ErasureCodeTpu, "encode_stripes", orig)
            await asyncio.sleep(0.06)
            await io.write_full("recovered", b"r" * (2 * 4096))
            assert not svc.degraded              # metric cleared
            assert not osd._mgr_health_metrics()["offload"]["degraded"]
            assert await io.read("recovered") == b"r" * (2 * 4096)
        finally:
            await harness.stop()
    run(body(), timeout=120)


def test_mgr_digest_raises_tpu_offload_degraded():
    """A daemon reporting offload.degraded digests into the
    TPU_OFFLOAD_DEGRADED health check (and drops out once clear)."""
    from ceph_tpu.mgr.daemon import DaemonStateIndex, MgrDaemon
    mgr = MgrDaemon.__new__(MgrDaemon)
    mgr.name = "x"
    mgr.daemon_index = DaemonStateIndex()
    mgr.daemon_index.report({
        "daemon_name": "osd.0", "service": "osd",
        "health_metrics": {"offload": {
            "degraded": True, "last_error": "RuntimeError: dev dead"}}})
    checks = mgr._build_digest()["checks"]
    assert "TPU_OFFLOAD_DEGRADED" in checks
    assert checks["TPU_OFFLOAD_DEGRADED"]["severity"] == "HEALTH_WARN"
    assert "osd.0" in checks["TPU_OFFLOAD_DEGRADED"]["detail"][0]
    mgr.daemon_index.report({
        "daemon_name": "osd.0", "service": "osd",
        "health_metrics": {"offload": {"degraded": False}}})
    assert "TPU_OFFLOAD_DEGRADED" not in mgr._build_digest()["checks"]


def test_admin_socket_commands_and_hot_config(tmp_path):
    """`ec offload status` / `ec offload flush` hooks + ec_offload_*
    hot-toggle through the daemon config observer."""
    async def body():
        harness = ClusterHarness(tmp_path, n_osds=2)
        await harness.start()
        osd = OSD(7, harness.mon_addrs,
                  admin_socket_path=str(tmp_path / "osd7.asok"))
        await osd.start()
        harness.osds[7] = osd
        try:
            svc = offload.get_service()
            st = osd.asok.execute({"prefix": "ec offload status"})
            assert "error" not in st
            res = st["result"]
            assert res["enabled"] is True
            assert {"max_batch_bytes", "linger_ms",
                    "max_queue_bytes"} <= set(res["settings"])
            fl = osd.asok.execute({"prefix": "ec offload flush"})
            assert fl["result"]["flushed_buckets"] == 0
            # hot-toggle: config set reaches the live service
            osd.config.set("ec_offload_linger_ms", 7.5)
            assert svc.linger_ms == 7.5
            osd.config.set("ec_offload_max_batch_bytes", 1 << 20)
            assert svc.max_batch_bytes == 1 << 20
            osd.config.set("ec_offload_enabled", False)
            assert svc.enabled is False
            osd.config.set("ec_offload_enabled", True)
            assert svc.enabled is True
        finally:
            osd.config.set("ec_offload_enabled", True)
            svc.apply_setting("ec_offload_linger_ms", 2.0)
            svc.apply_setting("ec_offload_max_batch_bytes", 8 << 20)
            await harness.stop()
    run(body(), timeout=120)


def test_offload_counters_ride_the_mgr_report(tmp_path):
    """The OSD's MgrClient merges the process-wide offload logger into
    its report (offload_* keys), so the mgr/exporter see the batching
    stats per reporting daemon."""
    async def body():
        harness = ClusterHarness(tmp_path, n_osds=2)
        await harness.start()
        try:
            osd = next(iter(harness.osds.values()))
            payload = {}

            class FakeConn:
                def send_message(self, msg):
                    payload.update(msg.payload)
            osd.mgr_client._conn = None
            osd.mgr_client._schema_keys_sent = None
            osd.mgr_client._last_sent = {}

            async def fake_ensure():
                return FakeConn()
            osd.mgr_client._ensure_session = fake_ensure
            assert await osd.mgr_client.send_report()
            assert any(k.startswith("offload_")
                       for k in payload["schema"])
            assert "offload_batches" in payload["counters"]
            assert payload["health_metrics"]["offload"] is not None
        finally:
            await harness.stop()
    run(body(), timeout=120)


# ---------------------------------------------------------------------------
# the ec_offload_* schema: nothing declared that nothing reads
# ---------------------------------------------------------------------------

def _offload_settings(svc) -> dict:
    st = svc.status()
    return {"enabled": st["enabled"], **st["settings"]}


@pytest.mark.parametrize("key", sorted(offload_service._DEFAULTS))
def test_every_offload_option_is_declared_and_consumed(key, monkeypatch):
    """Each default is a declared option, each declared option has a
    default, and a value set through a daemon Config shows in `ec offload
    status`: on the live service, or (startup-only options) on the next."""
    monkeypatch.setattr(offload_service, "_DEFAULTS",
                        dict(offload_service._DEFAULTS))
    opts = {o.name: o for o in offload.OFFLOAD_OPTIONS()}
    assert set(opts) == {"ec_offload_" + k for k in offload_service._DEFAULTS}
    opt = opts["ec_offload_" + key]
    if opt.type == "bool":
        value = not opt.default
    else:
        value = type(opt.default)(opt.default * 2 + 1)

    async def set_it():
        config = Config()
        offload.register_config(config)
        svc = offload.get_service()
        assert _offload_settings(svc)[key] == opt.default
        config.set(opt.name, value)
        return _offload_settings(svc)[key]

    async def next_service():
        return _offload_settings(offload.get_service())[key]

    live = run(set_it(), timeout=60)
    assert value in (live, run(next_service(), timeout=60))
