"""Async backfill (r4 verdict item #7): the PG serves client I/O while
a revived OSD backfills in the background; writes to not-yet-recovered
objects recover-on-write; recovery pushes share the host-wide
`osd_recovery_max_active` slots; stray replica objects are removed.

Reference: doc/dev/osd_internals/backfill_reservation.rst,
src/common/AsyncReserver.h, PrimaryLogPG wait_for_degraded_object."""
from __future__ import annotations

import asyncio

from tests.test_cluster import ClusterHarness, fast_timers, run  # noqa: F401


def test_client_ops_proceed_during_backfill(tmp_path, monkeypatch):
    """With a throttled, slowed recovery drain, client reads AND writes
    complete while the revived peer's backfill is still pending; a write
    to a pending object recovers it immediately (recover-on-write)."""
    async def body():
        c = ClusterHarness(tmp_path)
        try:
            await c.start()
            for osd in c.osds.values():     # one push at a time
                osd.config.set("osd_recovery_max_active", 1)
            cl = await c.client()
            await cl.pool_create("rbd", pg_num=1, size=3)
            io = cl.ioctx("rbd")
            n = 200
            for i in range(n):
                await io.write_full(f"o{i:04d}", bytes([i % 256]) * 512)
            # the victim must be a REPLICA: a revived primary recovers
            # itself synchronously before serving (no push backlog)
            from ceph_tpu.crush.osdmap import PG as PGId
            pool = cl.osdmap.get_pool("rbd")
            primary = cl.osdmap.primary(PGId(pool.id, 0))
            victim = next(i for i in c.osds if i != primary)
            store = c.osds[victim].store
            await c.kill_osd(victim)
            await c.wait_osd_down(victim)
            # the dead osd misses overwrites of EVERY object
            for i in range(n):
                await io.write_full(f"o{i:04d}", b"v2" + bytes([i % 256]))
            # slow every push so the backfill window is observable
            from ceph_tpu.osd import pg as pg_mod
            orig_push = pg_mod.PGInstance.send_push

            async def slow_push(self, *a, **kw):
                await asyncio.sleep(0.01)
                return await orig_push(self, *a, **kw)
            monkeypatch.setattr(pg_mod.PGInstance, "send_push", slow_push)
            await c.start_osd(victim, store=store)

            # find the primary once it is active with a pending backlog
            deadline = asyncio.get_running_loop().time() + 15
            prim = None
            while prim is None:
                for osd in c.osds.values():
                    for pg in osd.pgs.values():
                        if pg.is_primary() and pg.state == "active" \
                                and pg._pending_recovery:
                            prim = pg
                assert asyncio.get_running_loop().time() < deadline
                if prim is None:
                    await asyncio.sleep(0.02)
            backlog_at_start = len(prim._pending_recovery)
            assert backlog_at_start > 50, backlog_at_start

            # client I/O proceeds NOW, long before the backlog drains
            t0 = asyncio.get_running_loop().time()
            assert (await io.read("o0000")).startswith(b"v2")
            await io.write_full("fresh", b"new-while-backfilling")
            assert await io.read("fresh") == b"new-while-backfilling"
            elapsed = asyncio.get_running_loop().time() - t0
            assert elapsed < 2.0, f"client I/O stalled {elapsed}s"
            assert prim._pending_recovery, \
                "backfill finished before the I/O — window too small"

            # recover-on-write: touching a pending object recovers it
            pending_oid = next(iter(prim._pending_recovery))
            await io.write_full(pending_oid, b"touched")
            assert pending_oid not in prim._pending_recovery

            # drain completes; the revived osd converges on v2 state
            deadline = asyncio.get_running_loop().time() + 40
            while True:
                vpgs = [pg for pg in c.osds[victim].pgs.values()]
                done = (not prim._pending_recovery
                        and all(not pg.log.missing for pg in vpgs))
                if done:
                    break
                assert asyncio.get_running_loop().time() < deadline, \
                    f"backfill never drained " \
                    f"({len(prim._pending_recovery)} left)"
                await asyncio.sleep(0.1)
            vosd = c.osds[victim]
            stale = []
            for pg in vosd.pgs.values():
                for oid in pg.list_objects():
                    data = bytes(vosd.store.read(pg.backend.coll(),
                                                 pg.backend.ghobject(oid)))
                    if oid.startswith("o") and not data.startswith(b"v2") \
                            and oid != pending_oid:
                        stale.append(oid)
            assert not stale, stale[:5]
        finally:
            await c.stop()
    run(body())


def test_backfill_interrupted_by_failover_stays_consistent(tmp_path):
    """Primary dies mid-backfill: the recovering replica's PERSISTED
    missing set makes the next interval pull what it lacks before
    serving, so no object is lost or served stale."""
    async def body():
        c = ClusterHarness(tmp_path)
        try:
            await c.start()
            cl = await c.client()
            await cl.pool_create("rbd", pg_num=1, size=3)
            io = cl.ioctx("rbd")
            for i in range(60):
                await io.write_full(f"o{i:03d}", b"v1" + bytes([i]))
            victim = 1
            store = c.osds[victim].store
            await c.kill_osd(victim)
            await c.wait_osd_down(victim)
            for i in range(60):
                await io.write_full(f"o{i:03d}", b"v2" + bytes([i]))
            await c.start_osd(victim, store=store)
            # kill the primary while recovery may still be in flight
            prim = None
            deadline = asyncio.get_running_loop().time() + 15
            while prim is None:
                for i, osd in c.osds.items():
                    for pg in osd.pgs.values():
                        if pg.is_primary() and pg.state == "active":
                            prim = i
                assert asyncio.get_running_loop().time() < deadline
                await asyncio.sleep(0.02)
            if prim != victim:
                await c.kill_osd(prim)
                await c.wait_osd_down(prim)
            # every object still reads back v2 through the new interval
            for i in range(60):
                assert (await io.read(f"o{i:03d}")) == b"v2" + bytes([i])
        finally:
            await c.stop()
    run(body())
