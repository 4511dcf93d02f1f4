"""The scrub scheduler on a pool whose every PG spans every OSD (the
benchmark's layout: k+m = the number of OSDs), where each daemon's one
slot is wanted by every primary at once: the queue's order, background
rounds that finish idle and under reads, never more than
`osd_max_scrubs` rounds on a daemon, a taken slot that rejects at once,
a quiet peer that costs one bounded wait, and no slot left behind.

Reference contracts: OSD::sched_scrub (one PG at a time from a queue
ordered by when each is due), the scrub reserver (a replica without a
free slot rejects; the primary gives back what it holds and tries
again later)."""
from __future__ import annotations

import asyncio
import collections
import os
import time

import pytest

from ceph_tpu.crush.osdmap import PG
from ceph_tpu.osd import scrub as scrub_mod
from ceph_tpu.utils import flight

from tests.test_cluster import fast_timers, run  # noqa: F401
from tests.test_ec_rmw import make_ec_cluster

LAYOUTS = [(2, 1, 3, 8), (8, 3, 11, 16)]        # k, m, osds, pg_num
IDS = ["3osds_k2m1", "11osds_k8m3"]


# -- the queue, alone -------------------------------------------------------------

class _Fut:
    def __init__(self):
        self.value, self.answered = None, False

    def done(self):
        return self.answered

    def set_result(self, value):
        self.value, self.answered = value, True


def test_queue_orders_by_when_each_is_due():
    q = scrub_mod.ScrubQueue()
    a, b, c = PG(1, 0), PG(1, 1), PG(1, 2)
    q.sync([a, b], now=100.0)
    q.sync([a, b, c], now=100.5)
    # nothing is due before an interval has passed since it was seen
    assert q.next(100.9, 1.0, 1) is None
    assert q.wait(100.9, 1.0) == pytest.approx(0.1)
    assert q.next(101.2, 1.0, 1)[0] in (a, b)       # c: at 101.5
    q.done(a, 101.3, {"errors": 0})
    assert q.next(101.4, 1.0, 1)[0] == b
    q.done(b, 101.6, {"errors": 0})
    assert q.next(101.7, 1.0, 1)[0] == c            # the longest due
    q.done(c, 101.8, {"errors": 0})
    assert q.next(102.0, 1.0, 1) is None
    assert q.next(102.35, 1.0, 1)[0] == a
    # a PG that is no longer this OSD's leaves the queue
    q.sync([b, c], now=103.0)
    assert set(q.jobs) == {b, c}


def test_queue_puts_a_rejected_pg_back_and_goes_on():
    q = scrub_mod.ScrubQueue()
    a, b = PG(1, 0), PG(1, 1)
    q.sync([a], now=0.0)
    q.sync([a, b], now=0.1)
    assert q.next(2.0, 1.0, 1)[0] == a
    q.done(a, 2.0, {"reserve_failed": True})
    # a is back a short while later; meanwhile b has its turn
    assert q.next(2.0, 1.0, 1)[0] == b
    delay = q.jobs[a].not_before - 2.0
    assert scrub_mod.SCRUB_RETRY_S <= delay < 2 * scrub_mod.SCRUB_RETRY_S
    q.done(b, 2.1, {"errors": 0})
    assert q.next(2.1, 1.0, 1) is None
    assert q.wait(2.1, 1.0) == pytest.approx(delay - 0.1)
    assert q.next(2.0 + delay, 1.0, 1)[0] == a
    # a lost reservation is no round: a's place in the order is kept
    assert q.jobs[a].rounds == 0 and q.jobs[a].since == 0.0


def test_retry_delay_is_drawn_from_who_and_attempt_alone():
    seen = {scrub_mod.retry_delay(who, attempt)
            for who in range(11) for attempt in range(1, 9)}
    assert len(seen) > 60                   # they spread
    assert all(scrub_mod.SCRUB_RETRY_S <= d < 2 * scrub_mod.SCRUB_RETRY_S
               for d in seen)
    assert scrub_mod.retry_delay(7, 3) == scrub_mod.retry_delay(7, 3)


@pytest.mark.parametrize("primary", [0, 4, 10])
def test_the_turn_goes_round_the_acting_set(primary):
    """Daemons cannot see each other's queues: when a round gives their
    slots back, each holds its own next round back by `turn_hold`, one
    SCRUB_TURN_S for every place it stands behind that round's primary
    in the order of the ids, round again past the highest; the primary
    itself comes last. A member that is down has no place."""
    import types

    def member(me, down=()):
        host = types.SimpleNamespace(
            whoami=me,
            osdmap=types.SimpleNamespace(is_up=lambda o: o not in down))
        return types.SimpleNamespace(
            host=host, acting_peers=lambda: set(range(11)) - {me})

    step = scrub_mod.SCRUB_TURN_S
    holds = {me: scrub_mod.turn_hold(member(me), primary)
             for me in range(11)}
    order = sorted(holds, key=holds.get)
    assert order == [(primary + 1 + i) % 11 for i in range(11)]
    assert order[-1] == primary
    assert [holds[o] for o in order] == pytest.approx(
        [step * (i + 1) for i in range(11)])
    # osd.(primary+1) is down: the one after it is next in line
    down = ((primary + 1) % 11,)
    after = (primary + 2) % 11
    assert scrub_mod.turn_hold(member(after, down), primary) \
        == pytest.approx(step)
    assert scrub_mod.turn_hold(member(primary, down), primary) \
        == pytest.approx(step * 10)


@pytest.mark.parametrize("every", [1, 3])
def test_queue_goes_deep_every_nth_round_of_a_pg(every):
    q = scrub_mod.ScrubQueue()
    a = PG(2, 5)
    q.sync([a], now=0.0)
    deep = []
    for i in range(1, 7):
        pgid, d = q.next(10.0 * i, 1.0, every)
        deep.append(d)
        q.done(pgid, 10.0 * i, {"errors": 0})
    assert deep == [(i % every) == 0 for i in range(1, 7)]


def test_an_operators_request_goes_first_and_is_answered():
    q = scrub_mod.ScrubQueue()
    a, b = PG(1, 0), PG(1, 1)
    q.sync([a, b], now=0.0)
    fut = _Fut()
    assert not q.asked()
    q.request(b, True, fut)
    assert q.asked() and q.wait(0.0, 60.0) == 0.0
    assert q.next(0.0, 60.0, 4) == (b, True)    # not due, and deep
    q.done(b, 0.5, {"errors": 0, "deep": True})
    assert fut.value == {"errors": 0, "deep": True} and not q.asked()
    assert q.next(0.6, 60.0, 4) is None
    # a request whose reservations are all lost is answered in the end
    fut = _Fut()
    q.request(a, False, fut)
    now = 1.0
    for _ in range(scrub_mod.SCRUB_REQUEST_ATTEMPTS):
        assert not fut.answered
        now = max(now, q.jobs[a].not_before)
        assert q.next(now, 60.0, 4) == (a, False)
        q.done(a, now, {"reserve_failed": True})
    assert fut.value == {"reserve_failed": True}
    assert q.jobs[a].request is None
    # and one whose PG leaves this OSD, with None
    fut = _Fut()
    q.request(b, False, fut)
    q.sync([a], now=now)
    assert fut.answered and fut.value is None


# -- background rounds on the benchmark's layout ----------------------------------

def _slots_held(osd) -> int:
    sem = osd.scrub_reservations
    return sem.limit - sem._value


async def _watch(c, seconds, until=None, peak=None):
    """Sample every daemon's slots while `seconds` pass (or until
    `until()`): the most rounds any took part in at one instant."""
    peak = collections.Counter() if peak is None else peak
    t0 = time.monotonic()
    while time.monotonic() - t0 < seconds:
        for i, osd in c.osds.items():
            peak[i] = max(peak[i], _slots_held(osd))
            assert len(osd.scrub_reserver.grants) <= osd.scrub_reservations.limit
        if until is not None and until():
            break
        await asyncio.sleep(0.005)
    return peak


def _primaries(c):
    return [pg for osd in c.osds.values() for pg in osd.pgs.values()
            if pg.pool.name == "ecpool" and pg.is_primary()]


@pytest.mark.parametrize("loaded", [False, True], ids=["idle", "reads"])
@pytest.mark.parametrize("k,m,n_osds,pg_num", LAYOUTS, ids=IDS)
def test_background_scrub_finishes_a_round_of_every_pg(
        tmp_path, k, m, n_osds, pg_num, loaded):
    """Every PG's acting set is all the OSDs, so every primary wants
    every daemon's one slot. At a 1 s interval each PG gets a deep round
    within the bound, idle and under a closed loop of reads, and no
    daemon is ever in two rounds at once."""
    async def body():
        c, cl, io = await make_ec_cluster(tmp_path, k, m, n_osds,
                                          pg_num=pg_num)
        try:
            values = {f"o{i}": os.urandom(k * 4096 + i)
                      for i in range(2 * pg_num)}
            await asyncio.gather(*[io.write_full(n, v)
                                   for n, v in values.items()])
            pgs = _primaries(c)
            assert len(pgs) == pg_num
            assert all(len(pg.acting) == n_osds for pg in pgs)
            before = scrub_mod.scrub_perf().dump()
            for osd in c.osds.values():
                osd.config.set("osd_deep_scrub_every", 1)
                osd.config.set("osd_scrub_interval", 1.0)
            stop = False

            async def reader(start):
                names = sorted(values)
                i = start
                while not stop:
                    name = names[i % len(names)]
                    assert await io.read(name) == values[name]
                    i += 1
            readers = [asyncio.create_task(reader(4 * j))
                       for j in range(4 if loaded else 0)]
            peak = await _watch(
                c, 45.0, until=lambda: all(pg.last_scrub for pg in pgs))
            stop = True
            await asyncio.gather(*readers)
            assert all(pg.last_scrub for pg in pgs), \
                f"{sum(1 for pg in pgs if not pg.last_scrub)} of " \
                f"{pg_num} PGs never scrubbed"
            for pg in pgs:
                res = pg.last_scrub
                assert res["deep"] and res["errors"] == 0
                assert res["osds"] == list(range(n_osds))
                assert res["objects"] == len(pg.list_objects())
            assert max(peak.values()) == 1          # osd_max_scrubs
            after = scrub_mod.scrub_perf().dump()
            assert after["rounds"] - before["rounds"] >= pg_num
            assert after["aborts"] == before["aborts"]
        finally:
            await c.stop()
    run(body(), timeout=90)


def test_a_taken_slot_rejects_at_once_and_nothing_stays_held(tmp_path):
    """osd.2's slot is taken: the primary's round collects osd.1's
    grant, is rejected by osd.2 within a round trip, and every slot it
    held, its own and osd.1's, is free again as soon as its release
    has crossed; nothing waited for `osd_scrub_reserve_timeout`."""
    async def body():
        c, cl, io = await make_ec_cluster(tmp_path, 2, 1, 3, pg_num=8)
        try:
            await io.write_full("obj", os.urandom(8192))
            pg = next(pg for pg in c.osds[0].pgs.values()
                      if pg.is_primary() and pg.pool.name == "ecpool")
            assert c.osds[2].scrub_reservations.try_acquire()
            seq = flight.last_seq()
            t0 = time.monotonic()
            res = await pg.scrub(deep=True)
            assert time.monotonic() - t0 < 1.0
            assert res["reserve_failed"] is True and res["objects"] == 0
            (crumb,) = [e for e in flight.events_since(seq)["events"]
                        if e["type"] == "scrub_reserve_fail"]
            assert crumb["detail"]["stage"] == "osd.2"
            assert crumb["detail"]["reason"] == "rejected"
            assert pg.scrub_progress.state == "reserve_failed"
            assert _slots_held(c.osds[0]) == 0      # its own: at once
            deadline = time.monotonic() + 1.0
            while _slots_held(c.osds[1]):           # osd.1's: a message
                assert time.monotonic() < deadline
                await asyncio.sleep(0.005)
            assert c.osds[1].scrub_reserver.grants == set()
            assert _slots_held(c.osds[2]) == 1      # the one taken here
            # no write gate was ever closed
            await io.write_full("obj", os.urandom(8192))
            c.osds[2].scrub_reservations.release()
            res = await pg.scrub(deep=True)
            assert "reserve_failed" not in res and res["errors"] == 0
        finally:
            await c.stop()
    run(body())


@pytest.mark.parametrize("chunk_max", [32, 1], ids=["one_range", "ranges"])
def test_a_round_that_ends_sends_no_release(tmp_path, chunk_max):
    """A member gives its slot back with its map of the round's last
    range: that request names the reservation, no other does, and no
    `release` message is sent. When the round returns, every slot is
    free and every daemon's turn is held by its place behind the
    primary."""
    async def body():
        c, cl, io = await make_ec_cluster(tmp_path, 2, 1, 3, pg_num=1)
        try:
            for i in range(3):
                await io.write_full(f"obj{i}", os.urandom(8192))
            for osd in c.osds.values():
                osd.config.set("osd_scrub_chunk_max", chunk_max)
            (pg,) = _primaries(c)
            me = pg.host.whoami
            sent = []
            real = pg.host.send_osd

            async def send_osd(osd, msg):
                sent.append((type(msg).__name__, dict(msg.payload)))
                return await real(osd, msg)
            pg.host.send_osd = send_osd
            t0 = time.monotonic()
            res = await pg.scrub(deep=True)
            assert res["errors"] == 0 and res["objects"] == 3
            ops = [p["op"] for name, p in sent if name == "MOSDScrubReserve"]
            assert ops == ["reserve", "reserve"]
            asks = [p for name, p in sent if name == "MOSDRepScrub"]
            ranges = 1 if chunk_max == 32 else 3
            assert len(asks) == 2 * ranges
            naming = [p for p in asks if "release" in p]
            assert len(naming) == 2 and all(
                p["range"][1] is None for p in naming)
            for osd in c.osds.values():
                assert _slots_held(osd) == 0
                assert osd.scrub_reserver.grants == set()
            step = scrub_mod.SCRUB_TURN_S
            for i, osd in c.osds.items():
                behind = (i - me - 1) % 3
                hold = osd._scrub_hold_until - t0
                assert step * (behind + 1) <= hold < step * (behind + 1) + 1.0
        finally:
            await c.stop()
    run(body())


def test_the_turn_passes_no_daemon_over(tmp_path):
    """Background rounds at an interval shorter than a round of turns:
    every daemon always has a PG due, the rounds follow one another
    round the acting set, and after the first meeting nearly no
    reservation is lost."""
    async def body():
        c, cl, io = await make_ec_cluster(tmp_path, 2, 1, 3, pg_num=8)
        try:
            await asyncio.gather(*[io.write_full(f"o{i}", os.urandom(8192))
                                   for i in range(16)])
            owner = {str(pg.pgid): pg.host.whoami for pg in _primaries(c)}
            assert set(owner.values()) == {0, 1, 2}
            done = []
            real = scrub_mod._scrub_locked

            async def locked(pg, deep, progress):
                res = await real(pg, deep, progress)
                done.append((str(pg.pgid), progress.state))
                return res
            scrub_mod._scrub_locked = locked
            try:
                for osd in c.osds.values():
                    osd.config.set("osd_scrub_interval", 0.05)
                deadline = time.monotonic() + 30.0
                while sum(1 for _, st in done if st == "scrubbing") < 15:
                    assert time.monotonic() < deadline
                    await asyncio.sleep(0.01)
                for osd in c.osds.values():
                    osd.config.set("osd_scrub_interval", 3600.0)
            finally:
                scrub_mod._scrub_locked = real
            rounds = [owner[pgid] for pgid, st in done if st == "scrubbing"]
            lost = [pgid for pgid, st in done if st == "reserve_failed"]
            assert min(collections.Counter(rounds[:15]).values()) >= 3
            assert len(lost) <= 5
        finally:
            await c.stop()
    run(body(), timeout=60)


def test_a_daemons_own_taken_slot_sends_nothing(tmp_path):
    async def body():
        c, cl, io = await make_ec_cluster(tmp_path, 2, 1, 3, pg_num=8)
        try:
            pg = next(pg for pg in c.osds[0].pgs.values()
                      if pg.is_primary() and pg.pool.name == "ecpool")
            assert c.osds[0].scrub_reservations.try_acquire()
            seq = flight.last_seq()
            res = await pg.scrub()
            assert res["reserve_failed"] is True
            (crumb,) = [e for e in flight.events_since(seq)["events"]
                        if e["type"] == "scrub_reserve_fail"]
            assert crumb["detail"]["stage"] == "local"
            assert not pg._reserve_waiters
            assert _slots_held(c.osds[1]) == _slots_held(c.osds[2]) == 0
            c.osds[0].scrub_reservations.release()
        finally:
            await c.stop()
    run(body())


def test_a_peer_that_never_answers_costs_one_bounded_wait(
        tmp_path, monkeypatch):
    """osd.2 takes the slot and never says so. The primary waits
    `osd_scrub_reserve_timeout` once, gives back its own slot and
    osd.1's, and sends osd.2 a release too, so that the grant it never
    heard of is not held for good."""
    real = scrub_mod.handle_scrub_reserve
    quiet = {"on": True}

    def handle(host, pg, msg):
        answer = real(host, pg, msg)
        if host.whoami == 2 and answer is not None and quiet["on"]:
            answer.close()              # decided, never sent
            return None
        return answer
    monkeypatch.setattr(scrub_mod, "handle_scrub_reserve", handle)

    async def body():
        c, cl, io = await make_ec_cluster(tmp_path, 2, 1, 3, pg_num=8)
        try:
            await io.write_full("obj", os.urandom(8192))
            pg = next(pg for pg in c.osds[0].pgs.values()
                      if pg.is_primary() and pg.pool.name == "ecpool")
            for osd in c.osds.values():
                osd.config.set("osd_scrub_reserve_timeout", 0.5)
            seq = flight.last_seq()
            t0 = time.monotonic()
            res = await pg.scrub(deep=True)
            waited = time.monotonic() - t0
            assert res["reserve_failed"] is True
            assert 0.5 <= waited < 1.5
            (crumb,) = [e for e in flight.events_since(seq)["events"]
                        if e["type"] == "scrub_reserve_fail"]
            assert crumb["detail"]["stage"] == "osd.2"
            assert crumb["detail"]["reason"] == "timeout"
            deadline = time.monotonic() + 1.0
            while any(_slots_held(o) for o in c.osds.values()):
                assert time.monotonic() < deadline
                await asyncio.sleep(0.005)
            assert all(o.scrub_reserver.grants == set()
                       for o in c.osds.values())
            assert not pg._reserve_waiters
            quiet["on"] = False
            res = await pg.scrub(deep=True)
            assert "reserve_failed" not in res and res["errors"] == 0
        finally:
            await c.stop()
    run(body())


def test_raising_osd_max_scrubs_at_run_time_admits_more(tmp_path):
    """Every daemon's one slot is taken (a round of some other PG, held
    here by hand): a second round is rejected. `config set
    osd_max_scrubs 2` resizes the live pools and the round runs beside
    the first; set back to 1, the next is rejected again."""
    async def body():
        c, cl, io = await make_ec_cluster(tmp_path, 2, 1, 3, pg_num=8)
        try:
            for i in range(16):
                await io.write_full(f"o{i}", os.urandom(8192))
            pg = next(pg for pg in c.osds[0].pgs.values()
                      if pg.is_primary() and pg.pool.name == "ecpool")

            async def limits(n):
                for osd in c.osds.values():
                    osd.config.set("osd_max_scrubs", n)
                deadline = time.monotonic() + 1.0
                while any(o.scrub_reservations.limit != n
                          for o in c.osds.values()):
                    assert time.monotonic() < deadline
                    await asyncio.sleep(0.005)

            async def settled(held):
                deadline = time.monotonic() + 1.0
                while any(_slots_held(o) != held for o in c.osds.values()):
                    assert time.monotonic() < deadline
                    await asyncio.sleep(0.005)
            for osd in c.osds.values():
                assert osd.scrub_reservations.try_acquire()
            assert (await pg.scrub(deep=True))["reserve_failed"] is True
            await limits(2)
            res = await pg.scrub(deep=True)
            assert "reserve_failed" not in res and res["errors"] == 0
            assert res["osds"] == [0, 1, 2]
            await settled(1)                # the round's are back
            await limits(1)
            assert (await pg.scrub(deep=True))["reserve_failed"] is True
            for osd in c.osds.values():
                osd.scrub_reservations.release()
            await settled(0)
            res = await pg.scrub(deep=True)
            assert "reserve_failed" not in res and res["errors"] == 0
        finally:
            await c.stop()
    run(body())


def test_scrub_all_takes_its_turns_through_the_queue(tmp_path):
    """The operator's verb on every OSD at once, on the layout where
    every round wants every daemon: each PG is scrubbed, one round at a
    time a daemon, and every caller gets every result."""
    async def body():
        c, cl, io = await make_ec_cluster(tmp_path, 2, 1, 3, pg_num=8)
        try:
            for i in range(16):
                await io.write_full(f"o{i}", os.urandom(8192))
            peak = collections.Counter()
            watching = asyncio.create_task(_watch(c, 30.0, peak=peak))
            results = await asyncio.gather(*[
                osd.scrub_all(deep=True) for osd in c.osds.values()])
            watching.cancel()
            merged = {k: v for r in results for k, v in r.items()}
            assert len(merged) == 8
            for key, res in merged.items():
                assert res is not None and res["deep"], key
                assert "reserve_failed" not in res and res["errors"] == 0
            assert max(peak.values()) == 1
        finally:
            await c.stop()
    run(body(), timeout=60)
