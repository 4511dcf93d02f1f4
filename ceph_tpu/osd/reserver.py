"""Remote reservations: one mechanism, a slot pool for each user.

A daemon that is about to load its peers for a while (a scrub round:
every member of the acting set scans; a backfill: the target takes a
PG's objects) first claims a slot on each of them, so that the peer's
own limit (`osd_max_scrubs`, `osd_max_backfills`) bounds what others
do to it and not only what it starts itself (the reference's scrub
reserver, OSD::sched_scrub + MOSDScrubReserve, and its backfill
reservations, doc/dev/osd_internals/backfill_reservation.rst +
MBackfillReserve). The wire is four verbs on one message type:

  * `reserve`: the requester asks; the peer takes a slot of its pool if
    one is free (`try_acquire`) and answers `grant`, else `reject`.
    Nobody waits for a slot here: a daemon whose slots are taken says
    so at once, and the requester gives back what it holds and comes
    again later. A wait could cross another requester's;
  * `release`: the requester is done, or gave up; a release for a
    grant the peer does not hold is ignored, so a requester that may
    have been granted something (asked, then timed out) sends one
    whatever it heard.

A grant is keyed (pool, ps, tid, requester), the tid from the
requester's PG backend, so rounds of one PG never meet. It is lost
with its requester (`drop`: the map marked it down, so no release will
come) and, where the user says so, with its PG's interval.
"""
from __future__ import annotations

import asyncio
from typing import TYPE_CHECKING, Callable

from ceph_tpu.utils import sanitizer
from ceph_tpu.utils.dout import dout

if TYPE_CHECKING:
    from ceph_tpu.osd.pg import PGInstance


def retry_delay(base: float, who: int, attempt: int) -> float:
    """How long a requester that was refused waits before it asks
    again: `base` stretched by up to as much again. The stretch is
    drawn from `who` waits (a PG) and from the attempt and from nothing
    else, so that requesters refused together do not come back
    together and a schedule explorer's replays see the same delays."""
    return base * (1.0 + drawn(who, attempt))


def drawn(who: int, attempt: int) -> float:
    """A share in [0, 1) that only `who` and `attempt` decide."""
    return (who * 2654435761 + attempt * 40503) % 1021 / 1021.0


class RemoteReserver:
    """One kind of reservation on one daemon: the slots its peers may
    be granted, the grants it holds for them, and both halves of the
    wire protocol."""

    def __init__(self, host, slots, message, label: str,
                 of_interval: bool = False,
                 on_decided: Callable[[bool], None] | None = None,
                 on_given_back: Callable | None = None):
        self.host = host
        self.slots = slots              # AdjustableSemaphore
        self.message = message          # the wire type of this kind
        self.label = label              # names the pool to lockdep
        #: a grant belongs to its PG's interval: the user drops it when
        #: the interval ends, and none is made under a map older than
        #: the requester's (the newer one would end its interval here)
        self.of_interval = of_interval
        #: grants held here for others: (pool, ps, tid, requester)
        self.grants: set[tuple] = set()
        self.on_decided = on_decided            # (granted) a request was
        self.on_given_back = on_given_back      # (pg, requester) a slot is

    # -- the requester's half -------------------------------------------------

    def _send(self, osd: int, pg: "PGInstance", tid: int, op: str):
        return self.host.send_osd(osd, self.message(
            {"pgid": [pg.pgid.pool, pg.pgid.ps], "tid": tid,
             "from": self.host.whoami, "op": op,
             "epoch": self.host.osdmap.epoch}))

    async def ask(self, pg: "PGInstance", tid: int, osd: int,
                  timeout: float, asked: list[int]) -> str | None:
        """None: `osd` holds a slot for round `tid` of `pg`. Else why
        not. `asked` gains `osd` from the moment it may hold one,
        heard or not, and loses it only on its own `reject`: what is
        left there is owed a `release`. The wait for the answer is
        registered with lockdep under the PEER's pool, the inter-OSD
        edge the watchdog and the mgr's wait-for graph report for a
        peer that has gone quiet."""
        me = self.host.whoami
        fut = asyncio.get_running_loop().create_future()
        pg._reserve_waiters[(tid, osd)] = fut
        token = sanitizer.lockdep_wait_start(
            f"osd.{osd}:{self.label}", kind="remote_reserve",
            entity=f"osd.{me}", peer=osd, tid=tid, pgid=str(pg.pgid))
        try:
            await self._send(osd, pg, tid, "reserve")
            asked.append(osd)
            if await asyncio.wait_for(fut, timeout):
                return None
            asked.remove(osd)           # it said no: it holds nothing
            return "rejected"
        except asyncio.TimeoutError:
            return "timeout"
        except Exception as e:
            return f"{type(e).__name__}: {e}"
        finally:
            sanitizer.lockdep_wait_end(token)
            pg._reserve_waiters.pop((tid, osd), None)

    async def release(self, pg: "PGInstance", tid: int,
                      granted: list[int]) -> None:
        """Tell every peer of `granted` that round `tid` is over. Every
        one of them is told even while this task is being cancelled, or
        its slot stays taken for good; the cancellation is raised
        again when all have been."""
        interrupted: asyncio.CancelledError | None = None
        for peer in granted:
            try:
                await self._send(peer, pg, tid, "release")
            # radoslint: disable-next=cancellation-swallow
            except asyncio.CancelledError as e:
                interrupted = e
            except Exception as e:
                dout("osd", 2, f"{self.label}: release to osd.{peer} "
                               f"failed: {e}")
        if interrupted is not None:
            raise interrupted

    # -- the wire, decided where the message is dispatched --------------------

    def handle(self, pg: "PGInstance", msg):
        """Both halves, in the order the messages came, so a release
        that follows its reserve on the wire finds the grant.

        Peer (`op=reserve`): take a slot on the requester's behalf if
        one is free; the answer, grant or reject, is owed at once and
        is returned as a coroutine for the caller to run.

        Requester (`op=grant|reject`): resolve the round's waiter. An
        answer with no waiter comes from a peer the round stopped
        waiting for, which has been sent its release already.

        Anyone (`op=release`): free a slot granted to this requester."""
        p = msg.payload
        op, tid, frm = p.get("op"), p.get("tid"), p.get("from")
        if op == "reserve":
            key = (pg.pgid.pool, pg.pgid.ps, tid, frm)
            behind = self.of_interval \
                and p.get("epoch", 0) > self.host.osdmap.epoch
            granted = not behind and self.slots.try_acquire()
            if granted:
                self.grants.add(key)
            if self.on_decided is not None:
                self.on_decided(granted)
            return self._answer(pg, key, granted)
        if op in ("grant", "reject"):
            fut = pg._reserve_waiters.get((tid, frm))
            if fut is not None and not fut.done():
                fut.set_result(op == "grant")
        elif op == "release":
            self.give_back(pg, tid, frm)
        return None

    def give_back(self, pg: "PGInstance", tid: int, requester: int) -> None:
        """Free the slot held for round `tid` of `requester` on `pg`,
        if one is."""
        key = (pg.pgid.pool, pg.pgid.ps, tid, requester)
        if key in self.grants:
            self.grants.discard(key)
            self.slots.release()
            if self.on_given_back is not None:
                self.on_given_back(pg, requester)

    def drop(self, lost: Callable[[tuple], bool]) -> int:
        """Free every grant whose key `lost` names: no release will
        come for it (its requester is down, its interval is over)."""
        gone = [key for key in self.grants if lost(key)]
        for key in gone:
            self.grants.discard(key)
            self.slots.release()
        return len(gone)

    async def _answer(self, pg: "PGInstance", key: tuple,
                      granted: bool) -> None:
        _pool, _ps, tid, frm = key
        try:
            await self._send(frm, pg, tid, "grant" if granted else "reject")
        except BaseException as e:
            # the grant never reached the requester (this task reaped at
            # daemon stop, or the send failed), so nobody will ever
            # release it: hand the slot back
            self.drop(lambda k: k == key)
            if not isinstance(e, Exception):
                raise
            dout("osd", 2, f"{self.label}: answer to osd.{frm} failed: {e}")
