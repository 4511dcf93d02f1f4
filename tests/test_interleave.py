"""interlock qa tier: the schedule-interleaving explorer, the buffer
generation guards, and the lockset recorder — plus the seed sweeps
that drive the reactor/batching/pipelining suites through adversarial
schedules.

Covers the acceptance contract:
  * same seed => identical schedule log (digest) twice in a row;
  * the `osd_pg_pipeline_depth=1` legacy-serial path stays
    bit-identical under the explorer across 20 seeds (the PR 13
    fallback contract);
  * a seeded schedule reproducibly catches the PR 13 replica-splice
    bug re-introduced in a harness, and the generation guard catches
    staging-page reuse-after-recycle at the access site;
  * a multi-seed sweep of the pipelined-cluster workload (messenger
    batching + PG pipelining + offload dispatch under one roof) runs
    green with the sanitizer armed — guards and lockset recorder
    included. The >=100-seed version is the `slow` qa tier; tier-1
    runs the bounded smoke.
"""
from __future__ import annotations

import asyncio
import hashlib
import threading

import pytest

from ceph_tpu.qa import interleave
from ceph_tpu.utils import sanitizer

from tests.test_cluster import fast_timers, run  # noqa: F401
from tests.test_ec_rmw import make_ec_cluster

SMOKE_SEEDS = 5
DEPTH1_SEEDS = 20
FULL_SEEDS = 100


# -- explorer mechanics -------------------------------------------------------

async def _pingpong_workload():
    """Deterministic multi-task workload: schedule-sensitive output,
    no sockets/timers — the replay-contract probe."""
    q: asyncio.Queue = asyncio.Queue()
    out = []

    async def producer(i):
        for j in range(5):
            await q.put((i, j))
            await asyncio.sleep(0)
            if interleave.armed():
                await interleave.yield_point("producer")

    async def consumer():
        for _ in range(15):
            out.append(await q.get())

    await asyncio.gather(producer(0), producer(1), producer(2), consumer())
    return tuple(out)


def test_same_seed_identical_schedule_log():
    """One seed IS one schedule: two runs of the same workload under
    the same seed produce the same decision digest AND the same
    observable ordering; a different seed explores a different one."""
    async def one(seed):
        async with interleave.explore(seed) as ex:
            order = await _pingpong_workload()
            return ex.digest(), order, ex.decisions

    async def main():
        d1, o1, n1 = await one(7)
        d2, o2, n2 = await one(7)
        d8, o8, _ = await one(8)
        assert (d1, o1, n1) == (d2, o2, n2)
        assert d1 != d8                     # different seed, different log
        assert n1 > 0
        # and the shuffle genuinely perturbs execution order for SOME
        # seed (otherwise the explorer is a no-op): sweep until one
        # seed's ordering differs from the unexplored baseline
        base = await _pingpong_workload()
        perturbed = False
        for s in range(16):
            _, order, _ = await one(s)
            if order != base:
                perturbed = True
                break
        assert perturbed

    run(main())


def test_deferred_handle_cancel():
    """Cancelling a deferred callback's handle prevents it from ever
    running, across hops."""
    async def main():
        # defer_p=1: every callback defers, so the handle is a proxy
        async with interleave.explore(3, defer_p=1.0, max_defer=3):
            ran = []
            loop = asyncio.get_running_loop()
            h = loop.call_soon(ran.append, 1)
            h.cancel()
            for _ in range(8):              # drain every hop round
                await asyncio.sleep(0)
            assert ran == []
            # sanity: an uncancelled deferred callback still runs
            h2 = loop.call_soon(ran.append, 2)
            for _ in range(8):
                await asyncio.sleep(0)
            assert ran == [2] and not h2.cancelled()
    run(main())


def test_wrapper_composition_survives_non_lifo_uninstall():
    """The sanitizer's recorder and the explorer's shuffler both wrap
    loop.call_soon; uninstalling in NON-LIFO order must strip neither
    the surviving wrapper nor resurrect the dead one (each uninstall
    restores only when it is the top wrapper; a buried one degrades to
    pass-through and is reused on re-install)."""
    async def main():
        loop = asyncio.get_running_loop()
        # explorer first, sanitizer on top — then explorer exits FIRST
        interleave.install(loop, interleave.Explorer(1, defer_p=0.0))
        sanitizer.install(loop, view_guards=False)
        try:
            interleave.uninstall(loop)
            assert not interleave.armed()
            # the sanitizer's recorder must still be live: a foreign
            # call_soon is still recorded
            def foreign():
                try:
                    loop.call_soon(lambda: None)
                except RuntimeError:
                    pass
            t = threading.Thread(target=foreign)
            t.start()
            t.join()
            assert len(sanitizer.take_foreign_call_soon()) == 1
        finally:
            sanitizer.uninstall(loop)
            sanitizer.take_foreign_call_soon()
        # everything disarmed: callbacks flow plainly and re-install
        # of the explorer still works (reusing any in-chain wrapper)
        ran = []
        loop.call_soon(ran.append, 1)
        await asyncio.sleep(0)
        assert ran == [1]
        async with interleave.explore(2) as ex:
            await _pingpong_workload()
            assert ex.decisions > 0
    run(main())


def test_uninstall_restores_call_soon():
    async def main():
        loop = asyncio.get_running_loop()
        before = loop.call_soon
        async with interleave.explore(1):
            assert loop.call_soon is not before
            assert interleave.armed()
        assert not interleave.armed()
        ran = []
        loop.call_soon(ran.append, 1)
        await asyncio.sleep(0)
        assert ran == [1]
    run(main())


# -- buffer generation guards -------------------------------------------------

def test_generation_guard_catches_staging_reuse():
    """The staging-pool use-after-recycle class (the PR 13 eviction
    bug's family): a view over a staging page accessed after
    put_staging recycled it raises AT THE ACCESS SITE instead of
    reading the next batch's stripe."""
    from ceph_tpu.offload.service import _DeviceSlot, _DeviceState
    sanitizer.set_view_guards(True)
    try:
        slot = _DeviceSlot(_DeviceState("host", None), depth=2)
        page = slot.get_staging(4096)
        view = sanitizer.guard_view(memoryview(page), buf=page,
                                    label="staging")
        assert isinstance(view, sanitizer.GuardedView)
        assert len(view[0:16]) == 16            # live: windows fine
        trips0 = _san_counter("san_view_guard_trips")
        slot.put_staging(page)                  # the recycle point
        with pytest.raises(sanitizer.UseAfterRecycleError):
            bytes(view)
        with pytest.raises(sanitizer.UseAfterRecycleError):
            view[0:8].tobytes()                 # stale slice too
        assert _san_counter("san_view_guard_trips") >= trips0 + 2
        # a FRESH hand-out of the same page guards against the new
        # generation and reads cleanly
        page2 = slot.get_staging(4096)
        v2 = sanitizer.guard_view(memoryview(page2), buf=page2,
                                  label="staging")
        assert v2.nbytes == page2.nbytes
    finally:
        sanitizer.set_view_guards(False)


def test_data_view_message_guarded_end_to_end():
    """DATA_VIEW messages hand their rx window out guarded in
    sanitizer mode: normal access works (len/slice/bytes), and a
    simulated body-buffer recycle flips every outstanding view to
    raising — the pooled-rx forward-compat contract."""
    from ceph_tpu.msg import frames
    from ceph_tpu.msg.messages import Message, MOSDOp
    sanitizer.set_view_guards(True)
    try:
        m = MOSDOp({"op": "write"}, b"payload-bytes")
        m.seq = 1
        blob = bytes(frames.Frame(frames.Tag.MESSAGE,
                                  m.encode_segments()).encode())
        out = Message.decode_segments(frames.Frame.decode(blob).segments)
        assert isinstance(out.data, sanitizer.GuardedView)
        assert len(out.data) == len(b"payload-bytes")
        assert bytes(out.data) == b"payload-bytes"
        assert bytes(out.data[0:7]) == b"payload"
        # the guard unwraps cleanly at the tx boundary (resend path)
        assert out.encode_segments()[2] == b"payload-bytes"
        sanitizer.recycle_buffer(blob)          # simulated pooled-rx reuse
        with pytest.raises(sanitizer.UseAfterRecycleError):
            bytes(out.data)
        with pytest.raises(sanitizer.UseAfterRecycleError):
            out.encode_segments()
    finally:
        sanitizer.set_view_guards(False)


# -- lockset recorder (TSan-lite) --------------------------------------------

def test_lockset_recorder_flags_unlocked_cross_thread_write():
    from ceph_tpu.offload.service import _Topology
    sanitizer.set_lockset_recording(True)
    sanitizer.clear_lockset_conflicts()
    try:
        topo = _Topology()
        with topo.lock:
            topo.note("states", write=True)
        t = threading.Thread(target=topo.note,
                             args=("states",), kwargs={"write": True})
        t.start()
        t.join()
        conflicts = sanitizer.lockset_conflicts()
        assert conflicts and conflicts[0]["field"] == "states"
        assert conflicts[0]["owner"] == "_Topology"
        # the disciplined pattern reports nothing: both sides hold the
        # topology lock
        sanitizer.clear_lockset_conflicts()

        def locked_write():
            with topo.lock:
                topo.note("mesh_fns", write=True)

        with topo.lock:
            topo.note("mesh_fns", write=True)
        t = threading.Thread(target=locked_write)
        t.start()
        t.join()
        assert sanitizer.lockset_conflicts() == []
        # read/read needs no lock either
        topo.note("states", write=False)
        t = threading.Thread(target=topo.note, args=("states",),
                             kwargs={"write": False})
        t.start()
        t.join()
        assert sanitizer.lockset_conflicts() == []
        # IDENTITY, not name: holding a same-named lock on a DIFFERENT
        # topology must not mask the race (every _Topology's lock is
        # "offload_topology")
        sanitizer.clear_lockset_conflicts()
        other = _Topology()

        def wrong_lock_write():
            with other.lock:                    # wrong object's lock
                topo.note("states", write=True)

        with topo.lock:
            topo.note("states", write=True)
        t = threading.Thread(target=wrong_lock_write)
        t.start()
        t.join()
        assert len(sanitizer.lockset_conflicts()) == 1
        # dedup: the same conflicting pair re-accessing reports ONCE
        t = threading.Thread(target=wrong_lock_write)
        t.start()
        t.join()
        assert len(sanitizer.lockset_conflicts()) == 1
    finally:
        sanitizer.set_lockset_recording(False)
        sanitizer.clear_lockset_conflicts()


def test_foreign_call_soon_recorded_and_drained():
    """The sanitizer records loop.call_soon from a non-owner thread
    (before asyncio's debug-mode raise) — the conftest teardown gate's
    signal."""
    async def main():
        loop = asyncio.get_running_loop()
        sanitizer.install(loop, view_guards=False)
        try:
            def foreign():
                try:
                    loop.call_soon(lambda: None)
                except RuntimeError:
                    pass            # debug mode raises; already recorded
            t = threading.Thread(target=foreign)
            t.start()
            t.join()
        finally:
            sanitizer.uninstall(loop)
        events = sanitizer.take_foreign_call_soon()
        assert len(events) == 1
        assert "test_interleave" in events[0]["callback"]
        # drained: the conftest gate (which runs after us) sees none
        assert sanitizer.take_foreign_call_soon() == []
    run(main())


# -- re-introduced-bug detection ---------------------------------------------

def _buggy_insert(log, entry):
    """The pre-PR13 replica insert: the `version > head` guard DROPS
    out-of-order arrivals, leaving a failover-promoted log hole."""
    if entry.version > log.head:
        log.append(entry)


def test_seeded_schedule_catches_reverted_splice_bug():
    """Re-introduce the PR 13 replica-splice bug in a harness and let
    the explorer hunt it: concurrent fan-out tasks deliver v5/v6 to a
    replica log in schedule-dependent order. The REAL insert is
    invariant across every seed; the reverted one loses an entry on
    every seed whose schedule reorders the arrivals — and the failing
    seed replays the failure bit-identically."""
    from ceph_tpu.osd.pglog import LogEntry, PGLog

    async def deliver(insert_fn, seed):
        async with interleave.explore(seed, defer_p=0.5) as ex:
            log = PGLog()

            async def arrive(v):
                if interleave.armed():
                    await interleave.yield_point("replica_rx")
                insert_fn(log, LogEntry(version=(1, v), op="modify",
                                        oid=f"o{v}", reqid=(9, v)))

            await asyncio.gather(arrive(5), arrive(6), arrive(7))
            return [e.version for e in log.entries], ex.digest()

    async def main():
        want = [(1, 5), (1, 6), (1, 7)]
        healthy_insert = PGLog.insert
        failing = []
        for seed in range(DEPTH1_SEEDS):
            got, _ = await deliver(
                lambda lg, e: healthy_insert(lg, e), seed)
            assert got == want, f"seed {seed}: real splice diverged"
            got_bad, _ = await deliver(_buggy_insert, seed)
            if got_bad != want:
                failing.append(seed)
        # the sweep finds the bug...
        assert failing, "no schedule reordered the arrivals — explorer " \
                        "not perturbing"
        # ...and the finding seed REPLAYS: same wrong result, same digest
        s = failing[0]
        r1 = await deliver(_buggy_insert, s)
        r2 = await deliver(_buggy_insert, s)
        assert r1 == r2 and r1[0] != want

    run(main())


# -- cluster sweeps (the interleave tier) -------------------------------------

async def _serial_round(io, seed, n_objects=5):
    """The depth=1 workload: strictly sequential writes + reads. The
    PAYLOADS depend only on the object index (never the seed), so a
    round's fingerprint must be byte-equal to the unexplored control's
    — any schedule-dependent divergence breaks the comparison."""
    fingerprint = []
    for i in range(n_objects):
        oid = f"s{seed}-o{i}"                   # distinct oids per round
        payload = bytes([33 + i]) * (2 * 4096)
        await io.write_full(oid, payload)
        back = await io.read(oid)
        fingerprint.append((oid.split("-")[1],
                            hashlib.sha256(back).hexdigest(),
                            back == payload))
    return fingerprint


@pytest.mark.interleave
def test_depth1_legacy_serial_bit_identical_under_explorer(tmp_path):
    """The PR 13 fallback contract: `osd_pg_pipeline_depth=1` is the
    exact legacy inline path, so 20 seeded schedules (plus the
    unexplored control) must produce bit-identical results AND fully
    serial version allocation — no gaps, no reorder — every round."""
    async def body():
        c, cl, io = await make_ec_cluster(tmp_path, 2, 1, 3, pg_num=1)
        try:
            for o in c.osds.values():
                o.config.set("osd_pg_pipeline_depth", 1)

            def pg_head():
                pg = next(pg for osd in c.osds.values()
                          for pg in osd.pgs.values() if pg.is_primary())
                return pg, pg.log.head

            control = await _serial_round(io, 0)
            pg, head = pg_head()
            versions_per_round = head[1]    # writes since boot settle v
            for seed in range(1, DEPTH1_SEEDS + 1):
                async with interleave.explore(seed) as ex:
                    fp = await _serial_round(io, seed)
                    assert ex.decisions > 0     # the schedule moved
                # bit-identical outcome: same per-object content
                # fingerprint as the unexplored control — unconditional
                # (payloads are seed-independent by construction)
                assert [x[1:] for x in fp] == [x[1:] for x in control], \
                    f"seed {seed} diverged from the control round"
                pg2, head2 = pg_head()
                # serial allocation: exactly n_objects new versions,
                # contiguous, all settled (no pipelining artifacts)
                assert head2[1] == head[1] + len(fp)
                assert pg2.log.last_complete == head2
                head = head2
        finally:
            await c.stop()
    run(body())


async def _pipelined_round(c, io, seed, n_objects=8):
    """The pipelined workload: concurrent writes to distinct objects of
    one PG (depth=4), then read-back. Invariants, not orders: contents
    correct, log settled contiguously, windows drained."""
    payloads = {f"p{seed}-{i}": bytes([32 + (seed * 7 + i) % 90]) * (2 * 4096)
                for i in range(n_objects)}
    await asyncio.gather(*[io.write_full(k, v)
                           for k, v in payloads.items()])
    for k, v in payloads.items():
        assert await io.read(k) == v, f"seed {seed}: content diverged"
    for o in c.osds.values():
        assert o.op_queue.total_in_flight() == 0
        for pg in o.pgs.values():
            assert pg.log.last_complete == pg.log.head, \
                f"seed {seed}: unsettled log"


def _sweep_pipelined_cluster(tmp_path, seeds):
    """Shared harness for the smoke (tier-1) and full (slow) sweeps:
    one EC cluster, sanitizer ARMED (generation guards + lockset
    recorder + foreign-call_soon recording live on the data path),
    a fresh seeded schedule per round."""
    async def body():
        c, cl, io = await make_ec_cluster(tmp_path, 2, 1, 3, pg_num=1)
        loop = asyncio.get_running_loop()
        try:
            for o in c.osds.values():
                o.config.set("osd_pg_pipeline_depth", 4)
            sanitizer.install(loop, slow_callback_s=5.0)
            explored = set()
            for seed in seeds:
                async with interleave.explore(seed) as ex:
                    await _pipelined_round(c, io, seed)
                    explored.add(ex.digest())
            # distinct seeds really explored distinct schedules
            assert len(explored) > len(list(seeds)) // 2
            # and the lockset recorder saw no unlocked shared access
            assert sanitizer.lockset_conflicts() == []
        finally:
            sanitizer.uninstall(loop)
            sanitizer.clear_lockset_conflicts()
            await c.stop()
    run(body(), timeout=600)


@pytest.mark.interleave
def test_interleave_sweep_smoke(tmp_path):
    """Tier-1 slice of the qa sweep: SMOKE_SEEDS seeded schedules over
    the pipelined cluster (messenger batching + PG pipelining +
    offload dispatch under one roof) with the sanitizer armed."""
    _sweep_pipelined_cluster(tmp_path, range(SMOKE_SEEDS))


@pytest.mark.interleave
@pytest.mark.slow
def test_interleave_sweep_full(tmp_path):
    """The >=100-seed acceptance sweep (qa tier; excluded from tier-1
    by the `slow` marker)."""
    _sweep_pipelined_cluster(tmp_path, range(FULL_SEEDS))


def _san_counter(name: str) -> int:
    val = sanitizer.perf().dump().get(name, 0)
    return int(val if not isinstance(val, dict) else val.get("sum", 0))
