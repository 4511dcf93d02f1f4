"""Failure-storm resilience tests: deterministic fault injection,
degraded reads, crash reporting, hot recovery throttling, and
bandwidth-optimal (sub-chunk regenerating) recovery.

Covers the ISSUE-7 acceptance surface: seed-reproducible injection
sequences; EC reads served bit-identically with 1..m OSDs down on both
the host (jerasure) and offload (tpu) plugin paths; injected shard
bit-rot caught by the per-chunk crc gate; injected offload device
failures absorbed by the breaker's bit-identical host fallback;
`osd_max_backfills` / `osd_recovery_max_active` resizable mid-flight; crash records
surfaced as RECENT_CRASH with `crash ls`/`crash archive`; and CLAY
single-shard recovery fetching measurably fewer bytes than the
full-stripe gather.
"""
from __future__ import annotations

import asyncio
import types

import pytest

from ceph_tpu.qa import faultinject
from ceph_tpu.utils import crash
from ceph_tpu.utils.throttle import AdjustableSemaphore

from tests.test_cluster import ClusterHarness, fast_timers, run  # noqa: F401
from tests.test_ec_rmw import make_ec_cluster


@pytest.fixture(autouse=True)
def injector_clean():
    """Every test starts and ends with injection disarmed and empty."""
    faultinject.set_enabled(False)
    faultinject.reset(seed=0)
    yield
    faultinject.set_enabled(False)
    faultinject.reset(seed=0)


# ---------------------------------------------------------------------------
# determinism
# ---------------------------------------------------------------------------

class _FakeMsg:
    pass


def _drive(seed: int, n: int = 300) -> list:
    inj = faultinject.FaultInjector(seed=seed)
    inj.msg_drop, inj.msg_dup, inj.msg_delay = 0.2, 0.1, 0.1
    inj.bitrot = 0.3
    inj.device_fail = 0.2
    # a fixed consult schedule interleaving every site
    for i in range(n):
        inj.on_message(f"osd.{i % 3}", _FakeMsg())
        if i % 2 == 0:
            inj.maybe_bitrot(4096)
        if i % 3 == 0:
            inj.should_fail_device()
    return list(inj.log)


def test_same_seed_same_schedule_identical_injections():
    a, b = _drive(7), _drive(7)
    assert a == b and a, "same seed + schedule must replay identically"
    assert _drive(8) != a, "a different seed must diverge"


def test_per_site_counters_are_interleaving_independent():
    """Decisions key on (seed, site, n): consulting sites in a
    different cross-site order must not change any site's sequence."""
    inj1 = faultinject.FaultInjector(seed=3)
    inj2 = faultinject.FaultInjector(seed=3)
    inj1.msg_drop = inj2.msg_drop = 0.4
    inj1.device_fail = inj2.device_fail = 0.4
    for _ in range(50):                         # msg first, device after
        inj1.on_message("osd.0", _FakeMsg())
    for _ in range(50):
        inj1.should_fail_device()
    for _ in range(50):                         # opposite order
        inj2.should_fail_device()
    for _ in range(50):
        inj2.on_message("osd.0", _FakeMsg())
    key = lambda log: sorted(e for e in log)  # noqa: E731
    assert key(inj1.log) == key(inj2.log)


def test_oneshot_rules_match_exactly():
    inj = faultinject.FaultInjector(seed=0)
    inj.arm_oneshot(entity="client", msg_type="MOSDOpReply",
                    action="drop", count=1)

    class MOSDOpReply:
        pass

    class MPing:
        pass

    assert inj.on_message("osd.1", MOSDOpReply())[0] == "deliver"
    assert inj.on_message("client", MPing())[0] == "deliver"
    assert inj.on_message("client", MOSDOpReply())[0] == "drop"
    # consumed: the next matching message flows
    assert inj.on_message("client", MOSDOpReply())[0] == "deliver"


# ---------------------------------------------------------------------------
# degraded reads: 1..m OSDs down, host and offload plugin paths
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("plugin", ["jerasure", "tpu"])
def test_degraded_reads_bit_identical_with_1_to_m_down(tmp_path, plugin):
    async def body():
        c, cl, io = await make_ec_cluster(tmp_path, 2, 2, 4,
                                          plugin=plugin)
        try:
            import random
            rng = random.Random(11)
            model = {f"o{i}": rng.randbytes(rng.choice(
                [100, 2 * 4096, 3 * 2 * 4096 - 7])) for i in range(5)}
            for oid, data in model.items():
                await io.write_full(oid, data)
            # m=2: reads must stay bit-identical at every down count
            for down in (3, 2):
                await c.kill_osd(down)
                await c.wait_osd_down(down)
                for oid, data in model.items():
                    assert await io.read(oid) == data, \
                        (plugin, down, oid)
        finally:
            await c.stop()
    run(body())


def test_bitrot_on_local_shard_is_reconstructed_around(tmp_path):
    """A flipped byte in one shard blob fails its chunk crc: the read
    gather treats that shard as missing and decodes bit-identically
    from the survivors (the scrub/EC crc-gate contract)."""
    async def body():
        c, cl, io = await make_ec_cluster(tmp_path, 2, 1, 3)
        try:
            data = bytes(range(256)) * 64          # 2 stripes
            await io.write_full("rot", data)
            corrupted = 0
            for osd in c.osds.values():
                out = await osd._inject_bitrot("rot", offset=10)
                if out.get("injected"):
                    corrupted += 1
                    break
            assert corrupted == 1
            assert await io.read("rot") == data
        finally:
            await c.stop()
    run(body())


# ---------------------------------------------------------------------------
# injected device failure -> breaker fallback (offload path)
# ---------------------------------------------------------------------------

def test_injected_device_failure_falls_back_bit_identical():
    async def body():
        from ceph_tpu import offload
        from ceph_tpu.ec import registry
        from ceph_tpu.osd import ec_util
        impl = registry.factory("tpu", {"k": "4", "m": "2"})
        sinfo = ec_util.StripeInfo(4, 4 * 1024)
        svc = offload.get_service()
        svc.linger_ms = 1.0
        data = bytes(range(256)) * 64
        ref = ec_util.encode(sinfo, impl, data)
        faultinject.set_enabled(True)
        faultinject.arm_device_failures(1)
        base_fallback = svc.stats["fallback_ops"]
        out = await ec_util.encode_async(sinfo, impl, data, service=svc)
        assert out == ref                  # host fallback bit-identical
        assert svc.stats["fallback_ops"] > base_fallback
        await svc.drain()
    run(body(), timeout=60)


# ---------------------------------------------------------------------------
# hot-togglable recovery reservations
# ---------------------------------------------------------------------------

def test_adjustable_semaphore_shrink_blocks_while_overheld():
    """The review-flagged hazard: 3.10.9+ Semaphore.acquire fast-paths
    on locked(), so a shrink must never drive _value negative — it
    absorbs releases instead, and acquire() keeps BLOCKING while more
    holders than the new limit are in flight."""
    async def body():
        sem = AdjustableSemaphore(8)
        for _ in range(8):
            await sem.acquire()
        sem.resize(2)                    # shrink by 6 while 8 held
        assert sem.limit == 2
        assert sem.locked()              # NOT unbounded
        with pytest.raises(asyncio.TimeoutError):
            await asyncio.wait_for(sem.acquire(), 0.05)
        for _ in range(7):               # 6 absorbed, 1 freed
            sem.release()
        await asyncio.wait_for(sem.acquire(), 1)   # exactly one slot
        assert sem.locked()              # 2 held == new limit
        sem.release()
        sem.resize(3)                    # grow pays debt-free releases
        await asyncio.wait_for(sem.acquire(), 1)
    asyncio.run(asyncio.wait_for(body(), 30))


@pytest.mark.parametrize("option, pool_of", [
    ("osd_recovery_max_active", lambda osd: osd.recovery_active),
    ("osd_max_backfills", lambda osd: osd.backfill_local),
    ("osd_max_backfills", lambda osd: osd.backfill_reserver.slots)],
    ids=["recovery_max_active", "max_backfills_local",
         "max_backfills_remote"])
def test_recovery_slots_resize_live(tmp_path, option, pool_of):
    async def body():
        c = ClusterHarness(tmp_path, n_osds=1)
        try:
            await c.start()
            osd = c.osds[0]
            sem = pool_of(osd)
            assert isinstance(sem, AdjustableSemaphore)
            base = sem.limit
            assert base == osd.config.get(option)
            for _ in range(base):
                await sem.acquire()
            # grow: an extra slot appears without releasing anything
            osd.config.set(option, base + 4)
            await asyncio.sleep(0)      # let a threadsafe hop land
            await asyncio.wait_for(sem.acquire(), 2)
            assert sem.limit == base + 4
            # shrink below what is held (base+1 in flight): the pool
            # stays locked and refills only as holders release
            osd.config.set(option, 1)
            await asyncio.sleep(0)
            assert sem.limit == 1 and sem.locked()
            for _ in range(base + 1):
                sem.release()
            await asyncio.wait_for(sem.acquire(), 2)
            assert sem.locked()          # exactly the one new slot
            sem.release()
        finally:
            await c.stop()
    run(body())


# ---------------------------------------------------------------------------
# crash records -> health -> admin socket
# ---------------------------------------------------------------------------

def test_crash_records_surface_as_recent_crash(tmp_path):
    async def body():
        crash.reset()
        c = ClusterHarness(tmp_path, n_osds=1)
        try:
            await c.start()
            osd = c.osds[0]
            crash.record(f"osd.{osd.whoami}", RuntimeError("boom"))
            # a record site in a retry loop coalesces instead of
            # flooding the ring
            rec = crash.record(f"osd.{osd.whoami}", RuntimeError("boom"))
            assert rec["count"] == 2
            hm = osd._mgr_health_metrics()
            assert hm["recent_crashes"] == 1
            # the mgr digest turns any non-zero count into RECENT_CRASH
            from ceph_tpu.mgr.daemon import MgrDaemon
            st = types.SimpleNamespace(health_metrics={
                "recent_crashes": 1}, service="osd", age=0.1)
            fake = types.SimpleNamespace(
                name="x",
                daemon_index=types.SimpleNamespace(
                    daemons={"osd.0": st},
                    progress_events=lambda: []),
                FULL_RATIO=MgrDaemon.FULL_RATIO,
                NEARFULL_RATIO=MgrDaemon.NEARFULL_RATIO)
            digest = MgrDaemon._build_digest(fake)
            assert "RECENT_CRASH" in digest["checks"]
            assert "crash archive" in \
                digest["checks"]["RECENT_CRASH"]["summary"]
            # admin-socket verbs
            ls = osd.asok.execute({"prefix": "crash ls"})["result"] \
                if osd.asok else crash.ls()
            assert ls and ls[0]["exc_type"] == "RuntimeError"
            assert crash.archive() == 1
            assert osd._mgr_health_metrics()["recent_crashes"] == 0
            assert crash.ls() == []            # archived leave the list
            assert crash.ls(show_all=True)     # but stay inspectable
        finally:
            await c.stop()
            crash.reset()
    run(body())


def test_background_task_failure_posts_crash_record(tmp_path):
    async def body():
        crash.reset()
        c = ClusterHarness(tmp_path, n_osds=1)
        try:
            await c.start()
            osd = c.osds[0]

            async def explode():
                raise ValueError("injected bg failure")
            t = asyncio.get_running_loop().create_task(explode())
            osd._bg_tasks.add(t)
            t.add_done_callback(osd._bg_task_done)
            await asyncio.sleep(0.05)
            recs = crash.recent(f"osd.{osd.whoami}")
            assert recs and recs[0]["exc_type"] == "ValueError"
        finally:
            await c.stop()
            crash.reset()
    run(body())


# ---------------------------------------------------------------------------
# injected hang -> mark-down -> re-boot
# ---------------------------------------------------------------------------

def test_injected_hang_leads_to_mark_down_then_reboot(tmp_path):
    async def body():
        c = ClusterHarness(tmp_path, n_osds=3)
        try:
            await c.start()
            cl = await c.client()
            await cl.pool_create("rbd", pg_num=1, size=3)
            io = cl.ioctx("rbd")
            await io.write_full("o", b"x" * 1000)
            victim = c.osds[2]
            victim._set_hang(4.0)
            # peers report silence -> mon marks down (poll the healthy
            # osds' maps: the hung one cannot advance its own)
            deadline = asyncio.get_running_loop().time() + 20
            while True:
                maps = [c.osds[i].osdmap for i in (0, 1)]
                if all(2 in m.osds and not m.osds[2].up for m in maps):
                    break
                assert asyncio.get_running_loop().time() < deadline, \
                    "hung osd never marked down"
                await asyncio.sleep(0.1)
            # service continues degraded while the victim hangs
            assert await io.read("o") == b"x" * 1000
            # hang lifts -> wrongly-marked-down re-boot path brings it up
            deadline = asyncio.get_running_loop().time() + 20
            while True:
                m = c.osds[0].osdmap
                if 2 in m.osds and m.osds[2].up:
                    break
                assert asyncio.get_running_loop().time() < deadline, \
                    "hung osd never re-booted after the hang lifted"
                await asyncio.sleep(0.2)
        finally:
            await c.stop()
    run(body())


# ---------------------------------------------------------------------------
# bandwidth-optimal recovery: CLAY sub-chunk repair
# ---------------------------------------------------------------------------

def _repair_totals(c):
    fetched = full = 0
    for osd in c.osds.values():
        for pg in osd.pgs.values():
            fetched += getattr(pg.backend, "repair_bytes_fetched", 0)
            full += getattr(pg.backend, "repair_bytes_full", 0)
    return fetched, full


async def _wait_recovered(c, n_osds, timeout=60.0):
    from ceph_tpu.crush.crush import CRUSH_NONE
    deadline = asyncio.get_running_loop().time() + timeout
    while True:
        settled = True
        for osd in c.osds.values():
            for pg in osd.pgs.values():
                if pg.pool.type != "erasure":
                    continue
                if len(pg.acting) != n_osds or CRUSH_NONE in pg.acting:
                    settled = False
                elif pg.is_primary() and (pg.state != "active"
                                          or pg._pending_recovery):
                    settled = False
        if settled:
            return
        assert asyncio.get_running_loop().time() < deadline, \
            "cluster never reached clean after revive"
        await asyncio.sleep(0.2)


def test_decode_shards_whole_chunks_not_missliced_as_fragments():
    """Review-flagged hazard: a gather that topped up to >= d WHOLE
    chunks on a clay pool satisfies the sub-chunk repair plan's
    preconditions, but the buffers are not the plan's strided runs —
    decode_shards must treat them as whole chunks (correct, right-sized
    rebuild), with fragments=True reserved for real runs-fetches."""
    import numpy as np
    from ceph_tpu.ec import registry
    from ceph_tpu.osd import ec_util
    code = registry.factory("clay", {"k": "4", "m": "2", "d": "5"})
    chunk = code.get_chunk_size(4 * 4096)
    si = ec_util.StripeInfo(4, 4 * chunk)
    rng = np.random.default_rng(9)
    data = rng.integers(0, 256, 3 * si.stripe_width,
                        dtype=np.uint8).tobytes()
    shards = ec_util.encode(si, code, data)
    lost = 2
    # ALL five survivors as whole chunks: is_repair's preconditions
    # hold (>= d helpers, column group present), yet these are not
    # repair fragments
    avail = {i: shards[i] for i in range(6) if i != lost}
    rebuilt = ec_util.decode_shards(si, code, avail, [lost])
    assert rebuilt[lost] == shards[lost]

    async def via_service():
        from ceph_tpu import offload
        out = await ec_util.decode_shards_async(
            si, code, avail, [lost], service=offload.get_service())
        assert out[lost] == shards[lost]
    asyncio.run(asyncio.wait_for(via_service(), 60))


def test_clay_subchunk_repair_moves_less_than_full_stripe(tmp_path):
    """Single-shard recovery on a CLAY pool fetches d partial helper
    fragments (d/q chunks' worth) instead of k whole chunks — the
    repair-bytes ratio lands at d/(q*k) (= 0.625 for k=4,m=2,d=5),
    measurably below the full-stripe 1.0 — and the rebuilt shards are
    bit-identical (reads verify after recovery)."""
    async def body():
        c = ClusterHarness(tmp_path, n_osds=6)
        try:
            await c.start()
            cl = await c.client()
            await cl.command({"prefix": "osd erasure-code-profile set",
                              "name": "clayprof",
                              "profile": {"plugin": "clay", "k": "4",
                                          "m": "2", "d": "5"}})
            await cl.pool_create("claypool", pg_num=1,
                                 pool_type="erasure",
                                 erasure_code_profile="clayprof")
            io = cl.ioctx("claypool")
            pool = cl.osdmap.get_pool("claypool")
            obj = pool.stripe_width
            import random
            rng = random.Random(3)
            model = {f"o{i}": rng.randbytes(obj) for i in range(3)}
            for oid, data in model.items():
                await io.write_full(oid, data)

            victim = 5
            store = c.osds[victim].store
            await c.kill_osd(victim)
            await c.wait_osd_down(victim)
            # degraded writes the victim misses -> its missing set
            fresh = {f"d{i}": rng.randbytes(obj) for i in range(4)}
            for oid, data in fresh.items():
                await io.write_full(oid, data)

            f0, full0 = _repair_totals(c)
            await c.start_osd(victim, store=store)
            await _wait_recovered(c, 6)
            f1, full1 = _repair_totals(c)
            fetched, full = f1 - f0, full1 - full0
            assert full > 0 and fetched > 0
            ratio = fetched / full
            # true plan ratio is d/(q*k) = 0.625; a congested helper
            # can push the odd object onto the full-gather fallback,
            # so assert "measurably below full-stripe", not the exact
            # plan number (the bench stage reports the precise ratio)
            assert ratio < 0.9, \
                f"repair ratio {ratio:.3f} not below full-stripe"
            for oid, data in {**model, **fresh}.items():
                assert await io.read(oid) == data, oid
        finally:
            await c.stop()
    run(body())


def test_repair_knob_off_falls_back_to_full_gather(tmp_path):
    """osd_ec_repair_subchunks=false forces the classic full-stripe
    gather: the ratio returns to >= 1.0 (and recovery still works)."""
    async def body():
        c = ClusterHarness(tmp_path, n_osds=6)
        try:
            await c.start()
            cl = await c.client()
            for osd in c.osds.values():
                osd.config.set("osd_ec_repair_subchunks", False)
            await cl.command({"prefix": "osd erasure-code-profile set",
                              "name": "clayprof",
                              "profile": {"plugin": "clay", "k": "4",
                                          "m": "2", "d": "5"}})
            await cl.pool_create("claypool", pg_num=1,
                                 pool_type="erasure",
                                 erasure_code_profile="clayprof")
            io = cl.ioctx("claypool")
            obj = cl.osdmap.get_pool("claypool").stripe_width
            victim = 5
            store = c.osds[victim].store
            await c.kill_osd(victim)
            await c.wait_osd_down(victim)
            data = bytes(range(256)) * (obj // 256)
            await io.write_full("d0", data)
            f0, full0 = _repair_totals(c)
            await c.start_osd(victim, store=store)
            c.osds[victim].config.set("osd_ec_repair_subchunks", False)
            await _wait_recovered(c, 6)
            f1, full1 = _repair_totals(c)
            assert full1 - full0 > 0
            assert (f1 - f0) >= (full1 - full0)
            assert await io.read("d0") == data
        finally:
            await c.stop()
    run(body())
