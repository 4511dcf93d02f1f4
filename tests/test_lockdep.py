"""asynclockdep runtime tier: the acquisition-order graph, the live
wait-for-graph deadlock scan + watchdog, the throttle/semaphore
registry taps, the seeded interleave contract, and the distributed
crossed-scrub-reservation drill.

Reference contracts: src/common/lockdep.cc (order-graph cycle = bug at
ACQUIRE time, no deadlock needed), OSD::sched_scrub + MOSDScrubReserve
(acting-set scrub reservations: a taken slot rejects at once, so crossed
reservations cannot hold each other up).
"""
from __future__ import annotations

import asyncio
import threading
import time
import types

import pytest

from ceph_tpu.qa import interleave
from ceph_tpu.utils import flight, sanitizer
from ceph_tpu.utils.throttle import AdjustableSemaphore, Throttle

from tests.test_cluster import ClusterHarness, fast_timers, run  # noqa: F401


@pytest.fixture()
def lockdep():
    """Arm process-wide lockdep for one test, fast watchdog tick."""
    sanitizer.set_lockdep(True, stuck_wait_s=0.3)
    try:
        yield
    finally:
        sanitizer.set_lockdep(False)


def _wait_until(pred, timeout=3.0, step=0.02):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        v = pred()
        if v:
            return v
        time.sleep(step)
    return pred()


# -- order graph: inversion at acquire time ----------------------------------

def test_order_inversion_detected_at_acquire(lockdep):
    """A->B then B->A is an inversion the moment the SECOND order is
    attempted — no one has to actually deadlock (lockdep.cc's whole
    point). Witness renders edge by edge with sites."""
    a, b = sanitizer.make_lock("t1:A"), sanitizer.make_lock("t1:B")

    def fwd():
        with a:
            with b:
                pass

    def rev():
        with b:
            # inversion fires HERE, at the acquire attempt; the lock
            # itself is free so nothing blocks
            with a:
                pass

    t = threading.Thread(target=fwd)
    t.start()
    t.join()
    t = threading.Thread(target=rev)
    t.start()
    t.join()
    invs = sanitizer.lockdep_inversions()
    assert len(invs) == 1
    inv = invs[0]
    assert inv["cycle"][0] == inv["cycle"][-1]
    assert set(inv["cycle"]) == {"t1:A", "t1:B"}
    assert len(inv["edges"]) == 2
    for e in inv["edges"]:
        assert "test_lockdep" in e["site"]
    # the same cycle is reported once, not per re-acquisition
    t = threading.Thread(target=rev)
    t.start()
    t.join()
    assert len(sanitizer.lockdep_inversions()) == 1
    assert "t1:A -> t1:B" in sanitizer.lockdep_order_edges()


def test_cycle_digest_rotation_invariant():
    """The witness digest fingerprints the resource RING, not the
    discovery phase or the contexts involved: replays of the same
    scenario from either side agree bit for bit."""
    d1 = sanitizer._cycle_digest(["X", "Y"])
    d2 = sanitizer._cycle_digest(["Y", "X"])
    assert d1 == d2
    assert d1 != sanitizer._cycle_digest(["X", "Z"])
    assert len(d1) == 16


# -- live wait-for graph: scan + watchdog ------------------------------------

def test_thread_deadlock_scan_and_watchdog(lockdep):
    """Two threads crossed on real TrackedLocks: the scan names both
    parties, both resources, and a deterministic digest while the
    deadlock is LIVE; the watchdog thread notices it on its own within
    its tick and crumbs the flight ring."""
    a, b = sanitizer.make_lock("t2:A"), sanitizer.make_lock("t2:B")
    hold = threading.Barrier(2)

    def one(first, second):
        with first:
            hold.wait()
            # bounded: the test always unwinds
            if second.acquire(timeout=2.5):
                second.release()

    t1 = threading.Thread(target=one, args=(a, b), name="t2-fwd")
    t2 = threading.Thread(target=one, args=(b, a), name="t2-rev")
    t1.start()
    t2.start()
    scan = _wait_until(
        lambda: (s := sanitizer.deadlock_scan(stuck_s=0.05))["cycles"]
        and s)
    assert scan, "deadlock never seen by the scan"
    cyc = scan["cycles"][0]
    assert set(cyc["resources"]) == {"t2:A", "t2:B"}
    assert {"thread:t2-fwd", "thread:t2-rev"} <= set(cyc["tasks"])
    assert cyc["digest"] == sanitizer._cycle_digest(["t2:A", "t2:B"])
    for e in cyc["edges"]:
        assert e["waited_s"] >= 0.0 and "test_lockdep" in e["site"]
    # the watchdog's own sweep retains the detection + crumbs it
    last = _wait_until(
        lambda: (sanitizer.deadlock_dump().get("last_detection")
                 or {}).get("cycles"))
    assert last and last[0]["digest"] == cyc["digest"]
    crumbs = [e for e in flight.dump("deadlock_cycle")["events"]
              if e["detail"].get("digest") == cyc["digest"]]
    assert crumbs, "watchdog never crumbed the cycle"
    t1.join()
    t2.join()
    # both timed out and unwound: the graph drains
    assert sanitizer.deadlock_scan()["cycles"] == []


def test_deadlock_dump_shape(lockdep):
    """`deadlock dump` (the admin-socket verb's payload) carries the
    full attribution surface even when idle."""
    d = sanitizer.deadlock_dump()
    assert d["lockdep"] is True
    for key in ("order_edges", "inversions", "waits", "holders",
                "parked_tasks", "scan"):
        assert key in d
    l = sanitizer.make_lock("t3:only")
    with l:
        tok = sanitizer.lockdep_wait_start("t3:other", kind="lock",
                                           entity="osd.9", peer=1,
                                           tid=42)
        try:
            d = sanitizer.deadlock_dump()
            (w,) = [w for w in d["waits"]
                    if w["resource"] == "t3:other"]
            assert w["kind"] == "lock" and w["held"] == ["t3:only"]
            assert w["detail"] == {"entity": "osd.9", "peer": 1,
                                   "tid": 42}
            assert "t3:only" in d["holders"]
        finally:
            sanitizer.lockdep_wait_end(tok)


def test_wait_annotations_entity_filter(lockdep):
    """Each OSD ships only the waits IT owns: multiple daemons in one
    process (the test-harness topology) must not cross-report."""
    t1 = sanitizer.lockdep_wait_start("osd.1:slots", kind="remote_reserve",
                                      entity="osd.0", peer=1, tid=7)
    t2 = sanitizer.lockdep_wait_start("osd.0:slots", kind="remote_reserve",
                                      entity="osd.1", peer=0, tid=8)
    try:
        rows = sanitizer.wait_annotations(entity="osd.0", min_age_s=0.0)
        assert [r["resource"] for r in rows] == ["osd.1:slots"]
        assert rows[0]["peer"] == 1 and rows[0]["tid"] == 7
        assert sanitizer.wait_annotations(entity="osd.2",
                                          min_age_s=0.0) == []
        # too-young waits stay private
        assert sanitizer.wait_annotations(entity="osd.0",
                                          min_age_s=60.0) == []
    finally:
        sanitizer.lockdep_wait_end(t1)
        sanitizer.lockdep_wait_end(t2)


# -- registry taps: Throttle + AdjustableSemaphore (satellite) ---------------

def test_throttle_inversion_regression(lockdep):
    """Regression: a Throttle is a lock-order participant. Holding a
    lock while filling a throttle in one task, and holding throttle
    budget while taking the lock in another, is the same inversion
    TrackedLocks get flagged for."""
    th = Throttle("budget", 1)
    lk = sanitizer.make_lock("t4:L")

    def fwd():
        with lk:
            th.get(1)
            th.put(1)

    def rev():
        th.get(1)
        with lk:
            pass
        th.put(1)

    for fn in (fwd, rev):
        t = threading.Thread(target=fn)
        t.start()
        t.join()
    invs = [i for i in sanitizer.lockdep_inversions()
            if "throttle:budget" in i["cycle"]]
    assert len(invs) == 1
    assert set(invs[0]["cycle"]) == {"t4:L", "throttle:budget"}


def test_adjustable_semaphore_waits_and_holders(lockdep):
    """A NAMED semaphore registers its holder at acquire and its
    parked waiters in the wait-for graph; an anonymous one stays out
    of lockdep entirely (hot-path pools opt in by naming)."""
    async def main():
        sem = AdjustableSemaphore(1, name="t5:slots")
        sem.lockdep_detail = {"entity": "osd.5"}
        assert await sem.acquire()
        assert "t5:slots" in sanitizer.deadlock_dump()["holders"]

        async def second():
            assert await sem.acquire()
            sem.release()

        task = asyncio.create_task(second(), name="t5-waiter")
        await asyncio.sleep(0.05)
        rows = sanitizer.wait_annotations(entity="osd.5", min_age_s=0.0)
        assert [r["resource"] for r in rows] == ["t5:slots"]
        assert rows[0]["kind"] == "semaphore"
        assert rows[0]["task"] == "task:t5-waiter"
        sem.release()
        await task
        assert "t5:slots" not in sanitizer.deadlock_dump()["holders"]
        anon = AdjustableSemaphore(1)
        await anon.acquire()
        assert "t5:anon" not in sanitizer.deadlock_dump()["holders"]
        anon.release()

    asyncio.run(main())


# -- interleave tier: seeded schedules, deterministic witness ----------------

async def _grant_vs_write(inverted: bool) -> None:
    """Scrub-grant vs client-write miniature: both tasks touch the
    grant pool and the write gate. Legal order takes grant THEN gate
    on both sides; the inverted schedule crosses them."""
    grant = AdjustableSemaphore(1, name="il:scrub_grant")
    gate = AdjustableSemaphore(1, name="il:write_gate")

    async def scrubber():
        await grant.acquire()
        if interleave.armed():
            await interleave.yield_point("scrub:granted")
        await gate.acquire()
        gate.release()
        grant.release()

    async def writer():
        first, second = (gate, grant) if inverted else (grant, gate)
        await first.acquire()
        if interleave.armed():
            await interleave.yield_point("write:first")
        await second.acquire()
        second.release()
        first.release()

    await asyncio.gather(scrubber(), writer())


def test_interleave_grant_write_ordering(lockdep):
    """Seeded explorer drives scrub-grant vs client-write. Legal
    ordering stays silent across seeds; the inverted ordering fires on
    EVERY seed (the order graph is schedule-independent) and the same
    seed reproduces bit-identical witness digests."""
    async def one(seed, inverted):
        async with interleave.explore(seed) as ex:
            await _grant_vs_write(inverted)
            return ex.digest()

    def digests():
        return sorted(i["digest"]
                      for i in sanitizer.lockdep_inversions()
                      if "il:scrub_grant" in i["cycle"])

    for seed in range(3):
        asyncio.run(one(seed, inverted=False))
    assert digests() == [], "legal ordering must stay silent"

    sched1 = asyncio.run(one(11, inverted=True))
    wit1 = digests()
    assert len(wit1) == 1, "inverted ordering must fire deterministically"

    sanitizer.set_lockdep(False)
    sanitizer.set_lockdep(True, stuck_wait_s=0.3)   # reset state
    sched2 = asyncio.run(one(11, inverted=True))
    assert sched1 == sched2                  # same seed, same schedule
    assert digests() == wit1                 # ...and same witness


# -- mgr assembly: cross-daemon graph from annotations -----------------------

def _mgr_stub():
    from ceph_tpu.mgr.daemon import MgrDaemon
    return types.SimpleNamespace(
        DEADLOCK_EDGE_AGE_S=MgrDaemon.DEADLOCK_EDGE_AGE_S,
        _assemble_deadlock=MgrDaemon._assemble_deadlock)


def test_mgr_assembles_cross_daemon_cycle():
    m = _mgr_stub()
    rows = [
        {"entity": "osd.0", "resource": "osd.1:scrub_reservations",
         "kind": "remote_reserve", "age_s": 1.2, "task": "scrub-pg-1.0",
         "peer": 1, "tid": 7, "site": "scrub.py:1", "daemon": "osd.0"},
        {"entity": "osd.1", "resource": "osd.0:scrub_reservations",
         "kind": "remote_reserve", "age_s": 1.1, "task": "scrub-pg-1.3",
         "peer": 0, "tid": 9, "site": "scrub.py:1", "daemon": "osd.1"},
        # local wait: attribution only, no inter-daemon edge
        {"entity": "osd.1", "resource": "osd.1:scrub_reservations",
         "kind": "semaphore", "age_s": 1.0, "task": "dispatch",
         "peer": None, "tid": None, "site": "throttle.py:1",
         "daemon": "osd.1"},
    ]
    out = m._assemble_deadlock(m, rows)
    assert len(out["edges"]) == 2
    assert len(out["cycles"]) == 1
    assert set(out["cycles"][0][:-1]) == {"osd.0", "osd.1"}
    assert out["over_age_edges"] == []      # young edges: cycle only


def test_mgr_flags_over_age_edge_without_cycle():
    m = _mgr_stub()
    rows = [{"entity": "osd.2", "resource": "osd.3:scrub_reservations",
             "kind": "remote_reserve", "age_s": 99.0, "task": "scrub",
             "peer": 3, "tid": 1, "site": "s:1", "daemon": "osd.2"}]
    out = m._assemble_deadlock(m, rows)
    assert out["cycles"] == []
    assert len(out["over_age_edges"]) == 1
    assert out["over_age_edges"][0]["holder"] == "osd.3"


# -- distributed drill: crossed scrub reservations ---------------------------

def _primary_of(c, whoami, pool="rep"):
    """Some PG of `pool` whose primary is osd.whoami with the OTHER osd
    in its acting set."""
    for pg in c.osds[whoami].pgs.values():
        if pg.pool.name == pool and pg.is_primary() and pg.acting_peers():
            return pg
    return None


def test_crossed_scrub_reservations_are_rejected_not_parked(tmp_path):
    """Two primaries want each other's scrub slot and their own, at
    once. Nobody parks on a slot and all ask in one order, lowest id
    first: both go for osd.0's, its own primary has it, and the other
    is rejected at once while it holds nothing (far inside
    `osd_scrub_reserve_timeout`, which only bounds a peer that does not
    answer). So one round runs to the end and no round waits for
    another: a rejected reservation leaves no wait-for edge behind (no
    cycle for the watchdog, no annotation for the mgr) and no slot
    taken, and the PG that lost scrubs when it comes back, as the
    scheduler brings it."""
    async def body():
        sanitizer.set_lockdep(True, stuck_wait_s=0.3)
        c = ClusterHarness(tmp_path, n_osds=2)
        try:
            await c.start()
            cl = await c.client()
            await cl.pool_create("rep", pg_num=8, size=2)
            io = cl.ioctx("rep")
            for i in range(8):
                await io.write_full(f"obj{i}", b"x" * 64)
            pg0 = _primary_of(c, 0)
            pg1 = _primary_of(c, 1)
            assert pg0 is not None and pg1 is not None
            for osd in c.osds.values():
                osd.config.set("osd_scrub_reserve_timeout", 8.0)
            fails = flight.last_seq()
            t0 = time.monotonic()
            s0 = asyncio.create_task(pg0.scrub(), name="drill-scrub-0")
            s1 = asyncio.create_task(pg1.scrub(), name="drill-scrub-1")
            r0, r1 = await asyncio.gather(s0, s1)
            assert time.monotonic() - t0 < 2.0
            # osd.0's own primary had its slot; the other was turned
            # away there, and its round never began
            assert "reserve_failed" not in r0 and r0["errors"] == 0
            assert r0["osds"] == [0, 1]
            assert r1.get("reserve_failed") is True
            crumbs = [e for e in flight.events_since(fails)["events"]
                      if e["type"] == "scrub_reserve_fail"]
            assert [(e["entity"], e["detail"]["stage"],
                     e["detail"]["reason"]) for e in crumbs] == [
                (f"pg.{pg1.pgid}", "osd.0", "rejected")]
            # nothing waits, nothing stays held, on either daemon
            scan = sanitizer.deadlock_scan(stuck_s=0.0)
            assert scan["cycles"] == []
            assert not pg0._reserve_waiters and not pg1._reserve_waiters
            for who in (0, 1):
                assert sanitizer.wait_annotations(
                    entity=f"osd.{who}", min_age_s=0.0) == []
                sem = c.osds[who].scrub_reservations
                while sem.locked():     # the winner's release, crossing
                    assert time.monotonic() - t0 < 5.0
                    await asyncio.sleep(0.01)
                assert c.osds[who].scrub_reserver.grants == set()
            # the one that lost comes back and runs to the end
            res = await pg1.scrub()
            assert "reserve_failed" not in res and res["errors"] == 0
            assert res["osds"] == [0, 1]
            assert sanitizer.deadlock_scan()["cycles"] == []
        finally:
            sanitizer.set_lockdep(False)
            await c.stop()
    run(body())
