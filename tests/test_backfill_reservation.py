"""Recovery and backfill held to the reference's reservations
(doc/dev/osd_internals/backfill_reservation.rst): a PG pushes only while
it holds one of `osd_max_backfills` local slots on its primary and a
remote one on each target (`osd/reserver.py`, shared with scrub), up to
`osd_recovery_max_active` objects in flight a daemon.

The long scenario is the benchmark's recovery deployment, tiny: thirteen
OSDs, an 8+3 pool under writes, one OSD killed and marked out, then a
running one marked out. What had to be rebuilt, and every rebuilt byte,
is held to `benchmarks/reference_recovery.py`; the rule itself to its
checker, over the grants the spans record.
"""
from __future__ import annotations

import asyncio
import collections
import types

import numpy as np
import pytest

from benchmarks import reference, reference_recovery
from benchmarks.layer_metrics import recovery_spans
from ceph_tpu.crush.crush import CRUSH_NONE
from ceph_tpu.crush.osdmap import PG as PGId
from ceph_tpu.osd import pg as pg_mod
from ceph_tpu.utils import loopprof, tracer

from tests.test_cluster import fast_timers, run  # noqa: F401
from tests.test_ec_rmw import make_ec_cluster

CHUNK = 4096
POOL = "ecpool"


def _value(name: str, size: int = 40_000) -> bytes:
    seed = int.from_bytes(name.encode()[-4:], "big")
    return np.random.default_rng(seed).bytes(size)


def _acting(cl) -> dict:
    """pg -> acting set as the client's map has it, None for a hole."""
    pool = cl.osdmap.get_pool(POOL)
    return {ps: [None if o == CRUSH_NONE else o for o in
                 cl.osdmap.pg_to_up_acting_osds(PGId(pool.id, ps))[1]]
            for ps in range(pool.pg_num)}


def _by_pg(cl, names) -> dict:
    out: dict = {}
    for name in names:
        out.setdefault(cl.osdmap.object_to_pg(POOL, name).ps,
                       []).append(name)
    return out


async def _wait_clean(c, cl, timeout: float = 90.0) -> None:
    """Every PG of the pool active on its primary with nothing pending,
    every member activated with nothing missing, and no reservation
    left anywhere."""
    pool = cl.osdmap.get_pool(POOL)
    deadline = asyncio.get_running_loop().time() + timeout
    while True:
        await cl.monc.request_osdmap()
        why = None
        for ps, acting in _acting(cl).items():
            for osd in (o for o in acting if o is not None):
                inst = c.osds[osd].pgs.get(PGId(pool.id, ps))
                if inst is None or [None if o == CRUSH_NONE else o
                                    for o in inst.acting] != acting:
                    why = f"pg {ps} on osd.{osd}: map not caught up"
                elif inst.is_primary() and (
                        inst.state != "active" or inst._pending_recovery
                        or inst._deferred_activate or inst.log.missing):
                    why = f"pg {ps} primary osd.{osd}: {inst.state}, " \
                          f"{len(inst._pending_recovery)} pending"
                elif not inst.is_primary() and (
                        inst.state != "replica" or inst.log.missing
                        or not inst._active_event.is_set()):
                    why = f"pg {ps} replica osd.{osd}: {inst.state}, " \
                          f"{len(inst.log.missing)} missing"
        for osd in c.osds.values():
            if osd.backfill_reserver.grants or osd._backfills["local"]:
                why = f"osd.{osd.whoami} still holds a reservation"
        if why is None:
            return
        assert asyncio.get_running_loop().time() < deadline, why
        await asyncio.sleep(0.1)


def _slots_whole(c) -> None:
    """Nothing leaked: every pool of every daemon is back at its limit."""
    for osd in c.osds.values():
        for sem in (osd.backfill_local, osd.backfill_reserver.slots,
                    osd.recovery_active):
            assert sem._value == sem.limit and sem._debt == 0, \
                (osd.whoami, sem._value, sem.limit)
        assert osd._backfills == {"local": 0, "remote": 0}


def _spans(cursor: int) -> types.SimpleNamespace:
    """The spans since `cursor` as the benchmark's readers take them."""
    by: dict = collections.defaultdict(list)
    for s in tracer.collector().spans():
        if s["seq"] > cursor:
            by[s["name"]].append(s)
    return types.SimpleNamespace(spans=by)


async def _out(cl, osd: int) -> None:
    await cl.command({"prefix": "osd out", "ids": [osd]})


def _slow_pushes(monkeypatch, seconds: float) -> None:
    real = pg_mod.PGInstance.send_push

    async def slow(self, *a, **kw):
        await asyncio.sleep(seconds)
        return await real(self, *a, **kw)
    monkeypatch.setattr(pg_mod.PGInstance, "send_push", slow)


# -- the deployment, tiny ------------------------------------------------------

async def _thrash(tmp_path, max_backfills: int, second_out: bool) -> dict:
    """Writes, a kill, its mark-out, more writes, a live OSD's
    mark-out; clean after each. Returns what the checks need."""
    k, m = 8, 3
    c, cl, io = await make_ec_cluster(tmp_path, k, m, 13, pg_num=32)
    tracer.enable(max_spans=400_000)
    cursor = tracer.collector().last_seq()
    try:
        for osd in c.osds.values():
            osd.config.set("osd_max_backfills", max_backfills)
            # thirteen daemons' pings on one loop, beside five other
            # test workers: the quick grace of `fast_timers` marks
            # live OSDs down
            osd.config.set("osd_heartbeat_grace", 8.0)
        written: dict[str, bytes] = {}
        stop_bg = asyncio.Event()

        async def put(names) -> None:
            for name in names:
                written[name] = _value(name)
                await io.write_full(name, written[name])

        async def background() -> None:
            """The clients that never stop; their objects are read back
            and compared at rest, and are in no interval's plan."""
            i = 0
            while not stop_bg.is_set():
                await put([f"bg{i:04d}"])
                i += 1
                await asyncio.sleep(0.02)

        phases = [[f"a{i:04d}" for i in range(48)],
                  [f"b{i:04d}" for i in range(16)],
                  [f"c{i:04d}" for i in range(16)]]
        await put(phases[0])
        intervals = [{"acting": _acting(cl), "written": _by_pg(cl, phases[0])}]
        bg = asyncio.get_running_loop().create_task(background())
        dead, drained = 3, 7
        await c.kill_osd(dead)
        await c.wait_osd_down(dead, timeout=40.0)
        await cl.monc.request_osdmap()
        await put(phases[1])            # these lack the dead OSD's shard
        intervals.append({"acting": _acting(cl),
                          "written": _by_pg(cl, phases[1])})
        await _out(cl, dead)
        await _wait_clean(c, cl)
        await put(phases[2])
        intervals.append({"acting": _acting(cl),
                          "written": _by_pg(cl, phases[2])})
        if second_out:
            await _out(cl, drained)
            await _wait_clean(c, cl)
            intervals.append({"acting": _acting(cl), "written": {}})
        stop_bg.set()
        await bg
        for name, value in written.items():     # every acknowledged write
            assert await io.read(name) == value, name
        pool = cl.osdmap.get_pool(POOL)
        at_rest = {}
        for name in written:
            ps = cl.osdmap.object_to_pg(POOL, name).ps
            for pos, osd in enumerate(intervals[-1]["acting"][ps]):
                if osd is None:         # CRUSH ran out of tries: a hole
                    continue
                inst = c.osds[osd].pgs[PGId(pool.id, ps)]
                at_rest[(name, pos, osd)] = bytes(c.osds[osd].store.read(
                    inst.backend.coll(), inst.backend.ghobject(name)))
        _slots_whole(c)
        perf = {i: osd.perf.dump() for i, osd in c.osds.items()}
        parts = loopprof.dump()["parts_us"]
        return {"k": k, "m": m, "intervals": intervals, "written": written,
                "at_rest": at_rest, "ctx": _spans(cursor), "perf": perf,
                "parts": parts, "phases": phases}
    finally:
        tracer.disable()
        tracer.reset()
        await c.stop()


@pytest.fixture(scope="module")
def thrashed(tmp_path_factory):
    with pytest.MonkeyPatch.context() as mp:
        fast_timers.__wrapped__(mp)
        return run(_thrash(tmp_path_factory.mktemp("thrash"), 1, True),
                   timeout=300)


def test_every_shard_at_rest_is_the_references(thrashed):
    """Whole again on the eleven that stay in: every position of every
    object, rebuilt or written, holds the reference's bytes; a rebuilt
    one is computed both ways (the encoder's row, and a decode of the
    eight lowest survivors encoded again)."""
    k, m = thrashed["k"], thrashed["m"]
    plan = set().union(*reference_recovery.rebuild_plan(
        thrashed["intervals"]))
    assert plan
    for (name, pos, osd), blob in thrashed["at_rest"].items():
        value = thrashed["written"][name]
        if (name, pos, osd) in plan:
            assert reference_recovery.shard_differs(
                blob, value, k, m, CHUNK, pos, range(k + m)) == 0
        else:
            want = reference.expected_shards(value, k, m, CHUNK)[pos]
            assert np.array_equal(np.frombuffer(blob, np.uint8), want)
    # whole again on the eleven that stay in, but for the few positions
    # CRUSH's 50 tries leave unfilled with two of thirteen out
    holes = sum(o is None for act in
                thrashed["intervals"][-1]["acting"].values() for o in act)
    assert holes <= 3
    assert len(thrashed["at_rest"]) >= \
        (k + m) * len(thrashed["written"]) - holes * len(thrashed["written"])


def test_what_the_reference_plans_is_rebuilt_once_an_interval(thrashed):
    """Each (object, position, target) the reference names for the two
    mark-outs shows as one `ec_recover` span of its interval; what else
    was rebuilt is the background clients' (written across a change)."""
    plans = reference_recovery.rebuild_plan(thrashed["intervals"])
    assert plans[0] == plans[1] == set() and plans[2] and plans[3]
    spans = recovery_spans.recovers(thrashed["ctx"])
    seen = collections.Counter(
        (s["tags"]["oid"], s["tags"]["need"][0], s["tags"]["target"],
         s["tags"]["interval"]) for s in spans)
    assert max(seen.values()) == 1
    rebuilt = collections.Counter(key[:3] for key in seen)
    planned = plans[2] | plans[3]
    assert planned <= set(rebuilt)
    assert all(rebuilt[key] == 1 for key in planned
               if key not in plans[2] & plans[3])
    assert all(oid.startswith("bg") for oid, _p, _t in
               set(rebuilt) - planned)
    assert all(s["tags"]["pgid"] for s in spans)


def test_the_rule_holds_at_one_and_is_seen_broken_when_lifted(
        thrashed, tmp_path, monkeypatch):
    """The checker over the grants the spans record: never more than
    one reservation on any OSD in either role. The same recording made
    with the limit lifted (99) breaks it, so the checker can tell."""
    events = recovery_spans.reservation_events(thrashed["ctx"])
    assert len(events) >= 4 * 27        # a PG that lost a member, twice
    assert reference_recovery.check_reservations(events, 1) == []
    granted = recovery_spans.reserves(thrashed["ctx"], "granted")
    assert {s["tags"]["kind"] for s in granted} <= {"backfill", "log"}
    done = recovery_spans.dones(thrashed["ctx"], "done")
    assert len(done) == len(granted)
    assert sum(d["tags"]["objects"] for d in done) > 0
    assert sum(d["tags"]["bytes"] for d in done) > 0
    _slow_pushes(monkeypatch, 0.01)
    lifted = run(_thrash(tmp_path, 99, False), timeout=300)
    events = recovery_spans.reservation_events(lifted["ctx"])
    assert reference_recovery.check_reservations(events, 99) == []
    broken = reference_recovery.check_reservations(events, 1)
    assert broken and {role for _t, _o, role, _n in broken} <= \
        set(reference_recovery.ROLES)


def test_the_counters_and_the_loops_part(thrashed):
    """`perf dump` of every OSD has the four gauges and the two counts;
    at `osd_max_backfills` 1 no peak passes 1, some target granted and
    some refused; the loop account charged `osd.recovery`."""
    perf = thrashed["perf"]
    for dump in perf.values():
        assert {"backfill_reserve_granted", "backfill_reserve_rejected",
                "backfills_local", "backfills_remote",
                "backfills_local_peak", "backfills_remote_peak"} <= set(dump)
        assert dump["backfills_local"] == dump["backfills_remote"] == 0
        assert dump["backfills_local_peak"] <= 1
        assert dump["backfills_remote_peak"] <= 1
    assert sum(d["backfill_reserve_granted"] for d in perf.values()) >= 27
    assert max(d["backfills_remote_peak"] for d in perf.values()) == 1
    assert max(d["backfills_local_peak"] for d in perf.values()) == 1
    assert thrashed["parts"]["osd.recovery"] > 0
    waits = [s["tags"]["local_us"] + s["tags"]["remote_us"]
             for s in recovery_spans.reserves(thrashed["ctx"], "granted")]
    assert all(w >= 0 for w in waits)


# -- the corners, on a small pool ----------------------------------------------

async def _small(tmp_path, n_objects: int = 40):
    c, cl, io = await make_ec_cluster(tmp_path, 2, 2, 6, pg_num=8)
    values = {f"o{i:04d}": _value(f"o{i:04d}", 20_000)
              for i in range(n_objects)}
    for name, value in values.items():
        await io.write_full(name, value)
    return c, cl, io, values


async def _kill_and_out(c, cl, osd: int) -> None:
    await c.kill_osd(osd)
    await c.wait_osd_down(osd)
    await _out(cl, osd)


async def _ends_clean(c, cl, io, values) -> None:
    await _wait_clean(c, cl)
    for name, value in values.items():
        assert await io.read(name) == value, name
    _slots_whole(c)


def test_a_target_that_rejects_is_asked_again(tmp_path):
    """Every remote slot is taken by someone else when the mark-out
    comes: the PGs are refused, give back their local slot, and ask
    again; once the slots are free the backfill runs to its end."""
    async def body():
        c, cl, io, values = await _small(tmp_path)
        tracer.enable()
        cursor = tracer.collector().last_seq()
        try:
            for osd in c.osds.values():
                assert osd.backfill_reserver.slots.try_acquire()
            await _kill_and_out(c, cl, 5)
            await asyncio.sleep(1.5)
            rejected = sum(o.perf.dump()["backfill_reserve_rejected"]
                           for o in c.osds.values())
            assert rejected > 0
            assert all(not o.backfill_reserver.grants
                       for o in c.osds.values())
            assert any(pg._pending_recovery for o in c.osds.values()
                       for pg in o.pgs.values() if pg.is_primary())
            for osd in c.osds.values():     # the killed one is gone
                osd.backfill_reserver.slots.release()
            await _ends_clean(c, cl, io, values)
            spans = recovery_spans.reserves(_spans(cursor), "granted")
            assert spans and max(s["tags"]["rejects"] for s in spans) > 0
            assert all(s["tags"]["remote_us"] > 0 for s in spans
                       if s["tags"]["rejects"])
        finally:
            tracer.disable()
            tracer.reset()
            await c.stop()
    run(body(), timeout=120)


def test_a_grant_lost_to_an_interval_change_is_asked_for_again(
        tmp_path, monkeypatch):
    """A second mark-out meets the first backfill still running: the
    targets drop the grants of the interval that ended, the primaries
    let go and start over, and all ends clean."""
    _slow_pushes(monkeypatch, 0.05)

    async def body():
        c, cl, io, values = await _small(tmp_path, 60)
        tracer.enable()
        cursor = tracer.collector().last_seq()
        try:
            await _kill_and_out(c, cl, 5)
            deadline = asyncio.get_running_loop().time() + 20
            while not any(o.backfill_reserver.grants
                          for o in c.osds.values()):
                assert asyncio.get_running_loop().time() < deadline
                await asyncio.sleep(0.02)
            await _out(cl, 4)           # a live one, mid-backfill
            await _ends_clean(c, cl, io, values)
            ctx = _spans(cursor)
            ends = collections.Counter(
                d["tags"]["state"] for d in recovery_spans.dones(ctx))
            assert ends["interval_change"] >= 1 and ends["done"] >= 1
            assert reference_recovery.check_reservations(
                recovery_spans.reservation_events(ctx), 1) == []
        finally:
            tracer.disable()
            tracer.reset()
            await c.stop()
    run(body(), timeout=120)


def test_the_options_change_while_a_backfill_runs(tmp_path, monkeypatch):
    """`osd_max_backfills` 1 -> 4 and `osd_recovery_max_active` 3 -> 1
    on every OSD in the middle: the pools resize live, what is held
    stays held, and all ends clean at the new limits."""
    _slow_pushes(monkeypatch, 0.05)

    async def body():
        c, cl, io, values = await _small(tmp_path, 60)
        try:
            await _kill_and_out(c, cl, 5)
            deadline = asyncio.get_running_loop().time() + 20
            while not any(o._backfills["local"] for o in c.osds.values()):
                assert asyncio.get_running_loop().time() < deadline
                await asyncio.sleep(0.02)
            for osd in c.osds.values():
                osd.config.set("osd_max_backfills", 4)
                osd.config.set("osd_recovery_max_active", 1)
            await asyncio.sleep(0)
            await _ends_clean(c, cl, io, values)
            for osd in c.osds.values():
                assert osd.backfill_local.limit == 4
                assert osd.backfill_reserver.slots.limit == 4
                assert osd.recovery_active.limit == 1
        finally:
            await c.stop()
    run(body(), timeout=120)
