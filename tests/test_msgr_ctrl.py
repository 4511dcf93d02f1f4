"""The messenger's acks and control frames: a keepalive only from an
end that has received nothing for an interval; the ack the peer is owed
in the header of the next MESSAGE frame (msgr2's `ack_seq`), an ACK
frame only from a send with no MESSAGE frame, none at all on a lossy
session; one `writelines` for all a write-loop wake-up has to send — and
the bounds they exist for (`KEEPALIVE_TIMEOUT`, `IDLE_ACK_S`,
`ACK_EVERY`, replay exactly once, a bounded `_sent`) held as before, on
plain, secure and compressed sessions. A peer that speaks the old
header (no `ack` key, every ack a frame) is understood."""
from __future__ import annotations

import asyncio
import json
import os
import time

import pytest

from ceph_tpu.msg.frames import Frame, Onwire, Tag
from ceph_tpu.msg.messages import (Message, MOSDECSubOpWrite,
                                   MOSDECSubOpWriteReply, MPing, MPingReply,
                                   pack_batch)
from ceph_tpu.msg.messenger import (Connection, Dispatcher, Messenger,
                                    Policy, msgr_perf)
from ceph_tpu.msg.transport import SPILL_SIZE, Endpoint
from ceph_tpu.qa import faultinject

from tests.test_msg import Collector, Echo, run
from tests.test_msg_transport import _wait_for

KEY = b"0123456789abcdef"
MODES = {"crc": {}, "secure": {"auth_key": KEY, "secure": True},
         "compressed": {"compress": True}}
COUNTERS = ("ctrl_frames_tx", "tx_sends", "keepalives_skipped",
            "acks_carried_tx", "ack_frames_tx")


@pytest.fixture(params=list(MODES))
def mode(request):
    return MODES[request.param]


def _perf() -> dict:
    d = msgr_perf().dump()
    return {k: d[k] for k in COUNTERS}


def _delta(before: dict) -> dict:
    return {k: v - before[k] for k, v in _perf().items()}


class Tap:
    """Every send of every write loop, as (connection, the frames it
    carried, the parts handed to the transport)."""

    def __init__(self, monkeypatch):
        self.sends: list[tuple] = []
        framed: dict[int, list] = {}
        real_frame, real_write = Connection._frame_into, Endpoint.writelines
        tap = self

        def _frame_into(conn, parts, frame, onwire, nbytes):
            framed.setdefault(id(parts), [conn]).append(frame)
            return real_frame(conn, parts, frame, onwire, nbytes)

        def writelines(ep, parts):
            got = framed.pop(id(parts), None)
            if got is not None:
                tap.sends.append((got[0], got[1:], list(parts)))
            real_write(ep, parts)

        monkeypatch.setattr(Connection, "_frame_into", _frame_into)
        monkeypatch.setattr(Endpoint, "writelines", writelines)

    def tags(self, conn=None) -> list[list[Tag]]:
        return [[f.tag for f in frames] for c, frames, _p in self.sends
                if conn is None or c is conn]

    def count(self, *tags) -> int:
        return sum(t in tags for send in self.tags() for t in send)

    def acked(self, conn) -> list[int]:
        """What `conn`'s ACK frames said, in the order they left."""
        return [ack for tag, ack in self.acks(conn) if tag == Tag.ACK]

    def carried(self, conn) -> list[int]:
        """The `ack` of each MESSAGE frame `conn` sent, 0 where the
        header has no such key."""
        return [ack for tag, ack in self.acks(conn, 0)
                if tag == Tag.MESSAGE]

    def acks(self, conn, absent=None) -> list[tuple]:
        """(tag, ack) of every frame of `conn` that said one, by frame
        or by header, in the order they left."""
        out = []
        for c, frames, _p in self.sends:
            for f in frames if c is conn else ():
                head = json.loads(bytes(f.segments[0])) \
                    if f.tag in (Tag.ACK, Tag.MESSAGE) else {}
                ack = head[0] if f.tag == Tag.ACK \
                    else head.get("ack", absent)
                if ack is not None:
                    out.append((f.tag, ack))
        return out


async def _pair(mode, dispatcher, policy=None):
    server = Messenger("osd.1", **mode)
    server.add_dispatcher(dispatcher)
    addr = await server.bind()
    client = Messenger("osd.2", **mode)
    conn = await client.connect(addr, policy or Policy.lossless_peer())
    while not server._accepted:
        await asyncio.sleep(0.01)
    (sconn,) = server._accepted.values()
    return server, client, conn, sconn


async def _until(cond, timeout=10.0, what="condition"):
    t0 = time.monotonic()
    while not cond():
        if time.monotonic() - t0 > timeout:
            raise AssertionError(f"timed out waiting for {what}")
        await asyncio.sleep(0.005)
    return time.monotonic() - t0


async def _down(*messengers):
    for m in messengers:
        await m.shutdown()


# -- keepalives ---------------------------------------------------------------

def test_traffic_each_way_inside_every_interval_sends_no_keepalive(
        mode, monkeypatch):
    monkeypatch.setattr(Connection, "KEEPALIVE_INTERVAL", 0.5)

    async def main():
        tap = Tap(monkeypatch)
        server, client, conn, _s = await _pair(mode, Echo())
        replies = Collector()
        client.add_dispatcher(replies)
        before = _perf()
        for i in range(21):
            conn.send_message(MPing({"i": i}))
            await asyncio.sleep(0.1)
        await _wait_for(replies, 21)
        d = _delta(before)
        await _down(client, server)
        return tap, d

    tap, d = run(main())
    assert tap.count(Tag.KEEPALIVE, Tag.KEEPALIVE_ACK) == 0
    # both ends ticked four times in 2.1 s and found the connection live
    assert d["keepalives_skipped"] >= 6
    # what control frames there were are ACK frames, and nearly every
    # ack left in a header
    assert d["ctrl_frames_tx"] == tap.count(Tag.ACK) == d["ack_frames_tx"]
    assert d["acks_carried_tx"] >= 21
    # and no ACK frame left beside a MESSAGE frame: the header had it
    assert not any(Tag.MESSAGE in send and Tag.ACK in send
                   for send in tap.tags())
    assert d["ack_frames_tx"] <= 2


def test_a_connection_gone_quiet_is_probed_within_one_interval(
        mode, monkeypatch):
    """Nothing received for an interval: the next tick, at most one
    interval later, probes, and the answer is a frame like any other."""
    monkeypatch.setattr(Connection, "KEEPALIVE_INTERVAL", 0.3)

    async def main():
        tap = Tap(monkeypatch)
        server, client, conn, sconn = await _pair(mode, Echo())
        replies = Collector()
        client.add_dispatcher(replies)
        conn.send_message(MPing({"i": 0}))
        await _wait_for(replies, 1)
        quiet_from = max(conn._last_rx, sconn._last_rx)
        await _until(lambda: tap.count(Tag.KEEPALIVE) >= 1, 3.0,
                     "a KEEPALIVE")
        first = time.monotonic() - quiet_from
        await _until(lambda: tap.count(Tag.KEEPALIVE_ACK) >= 1, 3.0,
                     "its KEEPALIVE_ACK")
        await asyncio.sleep(1.0)
        n = tap.count(Tag.KEEPALIVE)
        await _down(client, server)
        return first, n

    first, n = run(main())
    assert 0.3 <= first < 2 * 0.3 + 0.6       # a tick, and a loaded host
    # an idle pair keeps probing, at most once a tick an end as before
    # (a tick's own answer is an interval old, less the round trip, by
    # the next: whether that tick probes is up to the timer's lateness)
    assert 1 <= n <= 12


def test_a_peer_that_stops_answering_is_faulted_within_the_timeout(
        mode, monkeypatch):
    """KEEPALIVE_TIMEOUT's bound: the peer goes silent with its socket
    open; what this end keeps SENDING does not stand for a live peer,
    and its transport is faulted between the timeout and one tick past
    it."""
    monkeypatch.setattr(Connection, "KEEPALIVE_INTERVAL", 0.3)
    monkeypatch.setattr(Connection, "KEEPALIVE_TIMEOUT", 1.2)

    async def main():
        tap = Tap(monkeypatch)
        server, client, conn, sconn = await _pair(mode, Echo())
        replies = Collector()
        client.add_dispatcher(replies)
        conn.send_message(MPing({"i": 0}))
        await _wait_for(replies, 1)
        # the peer's session loops die; nobody closes its socket
        dead = sconn._reader
        dead.transport.pause_reading()
        for t in list(sconn._tasks):
            t.cancel()
        await asyncio.sleep(0)
        gen, silent_from = conn._gen, conn._last_rx

        async def chatter():
            i = 1
            while True:
                conn.send_message(MPing({"i": i}))
                i += 1
                await asyncio.sleep(0.05)

        talking = asyncio.create_task(chatter())
        await _until(lambda: not conn.connected or conn._gen != gen, 5.0,
                     "the fault")
        took = time.monotonic() - silent_from
        talking.cancel()
        probes = sum(Tag.KEEPALIVE in send for send in tap.tags(conn))
        dead.close()
        await _down(client, server)
        return took, probes

    took, probes = run(main())
    assert 1.2 < took < 1.2 + 0.3 + 0.8       # a tick, and a loaded host
    assert probes >= 2          # probed from one interval of silence on


# -- acks ---------------------------------------------------------------------

def test_a_reply_carries_the_ack_in_its_header(mode, monkeypatch):
    """The reply a handler sends says in its header that the request it
    answers is done, and its send holds no ACK frame; the requester's
    replay queue is empty a round trip later, long before the idle
    flush could have run."""
    monkeypatch.setattr(Connection, "IDLE_ACK_S", 30.0)

    async def main():
        tap = Tap(monkeypatch)
        server, client, conn, sconn = await _pair(mode, Echo())
        replies = Collector()
        client.add_dispatcher(replies)
        before = _perf()
        conn.send_message(MPing({"i": 0}))
        assert len(conn._sent) == 1
        await _wait_for(replies, 1)
        took = await _until(lambda: not conn._sent, 2.0, "the ack")
        d = _delta(before)
        out = (tap.tags(sconn), tap.carried(sconn), tap.carried(conn), d,
               took, sconn._ack_timer)
        await _down(client, server)
        return out

    sends, carried, requests, d, took, timer = run(main())
    assert sends == [[Tag.MESSAGE]] and carried == [1]
    assert requests == [0]              # nothing owed yet: no key
    assert d["acks_carried_tx"] == 1
    assert d["ack_frames_tx"] == d["ctrl_frames_tx"] == 0
    assert d["tx_sends"] == 2           # the request, the reply
    assert took < 1.0 and timer is None     # the header disarmed the flush


def test_with_nothing_to_ride_the_ack_leaves_within_idle_ack_s(
        mode, monkeypatch):
    """IDLE_ACK_S's bound, as before: no reply, no header to carry it;
    the ACK frame leaves when the flush timer fires."""
    monkeypatch.setattr(Connection, "IDLE_ACK_S", 0.4)

    async def main():
        tap = Tap(monkeypatch)
        server, client, conn, sconn = await _pair(mode, Collector())
        before = _perf()
        for i in range(3):
            conn.send_message(MPing({"i": i}))
        took = await _until(lambda: not conn._sent, 3.0, "the ack")
        d = _delta(before)
        out = tap.tags(sconn), tap.acked(sconn), d, took
        await _down(client, server)
        return out

    sends, acked, d, took = run(main())
    assert sends == [[Tag.ACK]] and acked == [3]
    assert 0.3 < took < 0.4 + 0.8
    assert d["ctrl_frames_tx"] == d["ack_frames_tx"] == 1
    assert d["acks_carried_tx"] == 0


def test_sixteen_unacked_messages_force_an_ack_out(mode, monkeypatch):
    """ACK_EVERY's bound, as before: nothing to send and a flush timer
    far away, and the sixteenth finished message still sends the ack."""
    monkeypatch.setattr(Connection, "IDLE_ACK_S", 30.0)

    async def main():
        tap = Tap(monkeypatch)
        col = Collector()
        server, client, conn, sconn = await _pair(mode, col)
        for i in range(Connection.ACK_EVERY - 1):
            conn.send_message(MPing({"i": i}))
        await _wait_for(col, Connection.ACK_EVERY - 1)
        await asyncio.sleep(0.2)
        early = list(tap.tags(sconn)), len(conn._sent)
        conn.send_message(MPing({"i": 15}))
        took = await _until(lambda: not conn._sent, 3.0, "the ack")
        out = early, tap.acked(sconn), took
        await _down(client, server)
        return out

    early, acked, took = run(main())
    assert early == ([], Connection.ACK_EVERY - 1)
    assert acked == [Connection.ACK_EVERY] and took < 1.0


def test_an_overtaken_queued_ack_is_not_sent_twice(mode, monkeypatch):
    """A burst of requests: the threshold queues an ack, replies carry
    acks past it; every ack that leaves, by header or by frame, tells
    the peer something new, and a queued ack with nothing left to say
    sends nothing at all."""
    monkeypatch.setattr(Connection, "IDLE_ACK_S", 30.0)
    N = 3 * Connection.ACK_EVERY

    async def main():
        tap = Tap(monkeypatch)
        server, client, conn, sconn = await _pair(mode, Echo())
        replies = Collector()
        client.add_dispatcher(replies)
        for i in range(N):
            conn.send_message(MPing({"i": i}))
        await _wait_for(replies, N)
        await _until(lambda: not conn._sent, 3.0, "the last ack")
        sends = len(tap.tags(sconn))
        sconn._out.put_nowait(("ack", sconn._processed_seq))
        sconn._out.put_nowait(("ack", 1))
        await asyncio.sleep(0.2)
        out = ([a for _t, a in tap.acks(sconn)],
               len(tap.tags(sconn)) - sends)
        await _down(client, server)
        return out

    acked, more_sends = run(main())
    assert acked == sorted(set(acked)) and acked[-1] == N
    assert more_sends == 0


class _Gate(Dispatcher):
    """Counts dispatches; holds each handler until `release` is set."""

    def __init__(self):
        self.seen: list[int] = []
        self.entered = asyncio.Event()
        self.release = asyncio.Event()

    async def ms_dispatch(self, conn, msg):
        self.seen.append(msg.payload["i"])
        self.entered.set()
        await self.release.wait()
        return True


@pytest.mark.parametrize("fault", ["during_the_handler",
                                   "after_it_before_the_ack"])
def test_a_fault_between_dispatch_and_ack_replays_exactly_once(
        mode, monkeypatch, fault):
    """The transport dies after a message was handed to its handler and
    before any ack of it left. The sender replays it (nothing told it
    otherwise) or learns from the reconnect's in_seq that it need not;
    either way the handler has run once and the replay queue drains."""
    monkeypatch.setattr(Connection, "IDLE_ACK_S", 30.0)
    monkeypatch.setattr(Connection, "RECONNECT_BACKOFF", 0.05)

    async def main():
        tap = Tap(monkeypatch)
        gate = _Gate()
        server, client, conn, sconn = await _pair(mode, gate)
        conn.send_message(MPing({"i": 0}))
        await asyncio.wait_for(gate.entered.wait(), 5)
        if fault == "after_it_before_the_ack":
            gate.release.set()
            await _until(lambda: sconn._processed_seq == 1, 2.0,
                         "the handler")
        assert len(conn._sent) == 1 and not tap.acks(sconn)
        gen = conn._gen
        conn._writer.transport.abort()
        await _until(lambda: conn._gen > gen and conn.connected, 5.0,
                     "the reconnect")
        gate.release.set()
        # a second message shows the session whole, in order, once each
        conn.send_message(MPing({"i": 1}))
        await _until(lambda: len(gate.seen) >= 2, 5.0, "the second")
        conn.send_message(MPing({"i": 2}))
        await _until(lambda: len(gate.seen) >= 3, 5.0, "the third")
        seqs = conn.out_seq, sconn.in_seq
        out = list(gate.seen), seqs
        await _down(client, server)
        return out

    seen, seqs = run(main())
    assert seen == [0, 1, 2] and seqs == (3, 3)


# -- one send a wake-up -------------------------------------------------------

def test_items_queued_together_leave_in_one_send(mode, monkeypatch):
    async def main():
        tap = Tap(monkeypatch)
        col = Collector()
        server, client, conn, _s = await _pair(mode, col)
        before = _perf()
        for i in range(5):
            conn.send_message(MPing({"i": i}))
        conn._out.put_nowait(("keepalive", None))
        await _wait_for(col, 5)
        d = _delta(before)
        out = tap.tags(conn), d, [m.payload["i"] for m in col.messages]
        await _down(client, server)
        return out

    sends, d, order = run(main())
    assert sends[0] == [Tag.MESSAGE] * 5 + [Tag.KEEPALIVE]
    assert order == list(range(5))
    assert d["ctrl_frames_tx"] >= 1     # the probe, beside the five
    # the five and the probe were one send; the peer's answer another
    assert len(sends) == 1 and d["tx_sends"] <= 3


def test_a_large_frame_ends_the_send_and_still_goes_by_reference(
        mode, monkeypatch):
    """Gathering stops at the spill's size: two 100 KB messages queued
    with small ones leave one a send, each from where its bytes lie on a
    plain session (and as its own packed blob on the others)."""
    async def main():
        tap = Tap(monkeypatch)
        col = Collector()
        server, client, conn, _s = await _pair(mode, col)
        big = [os.urandom(100_000), os.urandom(SPILL_SIZE)]
        conn.send_message(MPing({"i": 0}))
        conn.send_message(MPing({"i": 1}, big[0]))
        conn.send_message(MPing({"i": 2}, big[1]))
        conn.send_message(MPing({"i": 3}))
        await _wait_for(col, 4)
        got = [bytes(m.data) for m in col.messages]
        out = [([f.tag for f in frames], parts)
               for c, frames, parts in tap.sends if c is conn], got, big
        await _down(client, server)
        return out

    sends, got, big = run(main())
    assert got == [b"", big[0], big[1], b""]
    assert [tags for tags, _p in sends] == [
        [Tag.MESSAGE, Tag.MESSAGE], [Tag.MESSAGE], [Tag.MESSAGE]]
    by_reference = [any(p is b for p in parts)
                    for (_t, parts), b in zip(sends, big)]
    assert by_reference == [not mode] * 2


def test_a_lossy_session_sends_no_ack_and_never_probes(mode, monkeypatch):
    """A lossy end keeps nothing to replay, so nobody would read an ack
    from its peer: none leaves, by header, by frame or by timer."""
    monkeypatch.setattr(Connection, "KEEPALIVE_INTERVAL", 0.2)
    monkeypatch.setattr(Connection, "IDLE_ACK_S", 0.2)
    N = Connection.ACK_EVERY + 4

    async def main():
        tap = Tap(monkeypatch)
        server, client, conn, sconn = await _pair(
            mode, Echo(), Policy.lossy_client())
        replies = Collector()
        client.add_dispatcher(replies)
        before = _perf()
        for i in range(N):
            conn.send_message(MPing({"i": i}))
        await _wait_for(replies, N)
        await asyncio.sleep(0.7)
        out = (tap.acks(sconn) + tap.acks(conn), tap.count(Tag.ACK),
               tap.count(Tag.KEEPALIVE), _delta(before),
               (len(conn._sent), len(sconn._sent)),
               (conn._ack_timer, sconn._ack_timer))
        await _down(client, server)
        return out

    acks, frames, probes, d, sent, timers = run(main())
    assert acks == [] and frames == 0 and probes == 0
    assert d["acks_carried_tx"] == d["ack_frames_tx"] == 0
    assert d["ctrl_frames_tx"] == 0
    assert sent == (0, 0) and timers == (None, None)


# -- what an ack may ride on, and what it may not forget -------------------------

def test_one_message_on_two_connections_carries_each_ones_own_ack(
        mode, monkeypatch):
    """The ack is the connection's to give, at the time of the send:
    the same object sent to two peers tells each what IT is owed, and
    remembers neither."""
    monkeypatch.setattr(Connection, "IDLE_ACK_S", 30.0)

    async def main():
        tap = Tap(monkeypatch)
        hub = Messenger("osd.0", **mode)
        got = Collector()
        hub.add_dispatcher(got)
        peers, conns, sconns = [], [], []
        for name in ("osd.1", "osd.2"):
            peer = Messenger(name, **mode)
            peer.add_dispatcher(Collector())
            addr = await peer.bind()
            conns.append(await hub.connect(addr, Policy.lossless_peer()))
            while not peer._accepted:
                await asyncio.sleep(0.01)
            sconns.extend(peer._accepted.values())
            peers.append(peer)
        for sconn, n in zip(sconns, (2, 5)):
            for i in range(n):
                sconn.send_message(MPingReply({"i": i}))
        await _wait_for(got, 7)
        await _until(lambda: [c._processed_seq for c in conns] == [2, 5],
                     2.0, "the hub's handlers")
        msg = MPing({"both": True})
        for conn, peer in zip(conns, peers):
            conn.send_message(msg)
            await _wait_for(peer.dispatchers[0], 1)
        await _until(lambda: not any(sc._sent for sc in sconns), 2.0,
                     "the peers' replay queues to empty")
        out = [tap.carried(c) for c in conns], msg.ack
        await _down(hub, *peers)
        return out

    carried, remembered = run(main())
    assert carried == [[2], [5]] and remembered == 0


def test_a_replayed_message_carries_the_ack_of_the_time_it_leaves(
        mode, monkeypatch):
    """First sent with nothing owed, replayed after a message from the
    peer was handled: the second encoding says so, the first did not."""
    monkeypatch.setattr(Connection, "IDLE_ACK_S", 30.0)
    monkeypatch.setattr(Connection, "RECONNECT_BACKOFF", 0.05)

    async def main():
        tap = Tap(monkeypatch)
        gate = _Gate()
        server, client, conn, sconn = await _pair(mode, gate)
        got = Collector()
        client.add_dispatcher(got)
        conn.send_message(MPing({"i": 0}))
        await asyncio.wait_for(gate.entered.wait(), 5)
        sconn.send_message(MPingReply({"unasked": True}))
        await _wait_for(got, 1)
        await _until(lambda: conn._processed_seq == 1, 2.0, "the handler")
        assert len(conn._sent) == 1 and len(sconn._sent) == 1
        gen = conn._gen
        conn._writer.transport.abort()
        await _until(lambda: conn._gen > gen and conn.connected, 5.0,
                     "the reconnect")
        gate.release.set()
        await _until(lambda: not sconn._sent, 2.0, "the peer's trim")
        conn.send_message(MPing({"i": 1}))
        await _until(lambda: len(gate.seen) >= 2, 5.0, "the second")
        out = tap.carried(conn), list(gate.seen)
        await _down(client, server)
        return out

    carried, seen = run(main())
    # ping 0, ping 0 again with the ack, ping 1 with nothing new to say
    assert carried == [0, 1, 0] and seen == [0, 1]


def test_a_one_way_stream_keeps_a_bounded_replay_queue(mode, monkeypatch):
    """1,000 messages one way and none back: nothing ever carries an
    ack, and the ACK frames of ACK_EVERY alone keep `_sent` to what the
    peer has not handled yet, what it has handled since its last ack
    (under ACK_EVERY when its write loop runs), and the acks in flight."""
    monkeypatch.setattr(Connection, "IDLE_ACK_S", 30.0)
    N, WINDOW = 1000, 8

    async def main():
        tap = Tap(monkeypatch)
        col = Collector()
        server, client, conn, sconn = await _pair(mode, col)
        before = _perf()
        peak = owed = 0
        for i in range(N):
            conn.send_message(MPing({"i": i}))
            await _until(lambda: conn.out_seq - sconn._processed_seq
                         < WINDOW, 5.0, "the peer's handlers")
            peak = max(peak, len(conn._sent))
            owed = max(owed, sconn._processed_seq - sconn._last_acked_in)
        await _wait_for(col, N)
        await asyncio.sleep(0.1)
        out = (peak, owed, len(conn._sent), _delta(before),
               tap.carried(conn) + tap.carried(sconn))
        await _down(client, server)
        return out

    peak, owed, left, d, carried = run(main())
    assert owed <= Connection.ACK_EVERY + WINDOW
    assert peak <= 2 * (Connection.ACK_EVERY + WINDOW)
    assert left < Connection.ACK_EVERY
    assert N // Connection.ACK_EVERY - 1 <= d["ack_frames_tx"] \
        <= N // Connection.ACK_EVERY + 1
    assert d["acks_carried_tx"] == 0 and not any(carried)


# -- the wire is what it was ----------------------------------------------------

def _fed(chunks, onwire=None, in_seq=0) -> Connection:
    """A connection whose read loop has consumed `chunks`, each fed to
    its reader in one piece."""
    async def main():
        conn = Connection(Messenger("osd.9"), None, Policy.lossless_peer(),
                          initiator=False)
        for seq in (1, 2, 3):
            m = MPing({"i": seq})
            m.seq = seq
            conn._sent.append(m)
        conn.out_seq = 3
        conn.in_seq = in_seq
        reader = asyncio.StreamReader()
        for c in chunks:
            reader.feed_data(c)
        reader.feed_eof()
        with pytest.raises(asyncio.IncompleteReadError):
            await conn._read_loop(reader, onwire)
        await conn.close()          # a delayed delivery is a task of its
        return conn

    return run(main())


def _state(conn: Connection) -> tuple:
    out, queued = [], []
    while not conn._out.empty():
        out.append(conn._out.get_nowait())
    while not conn._dispatch_q.empty():
        _gen, m = conn._dispatch_q.get_nowait()
        queued.append((type(m).__name__, m.seq, m.payload, bytes(m.data)))
    return ([m.seq for m in conn._sent], conn.in_seq, out, queued)


def test_frames_alone_and_frames_grouped_read_to_the_same_state():
    """What the parent put on the wire, one frame a send, and what the
    write loop groups now are the same bytes; a read loop takes either
    to the same state."""
    reply = MPingReply({"i": 7}, b"payload")
    reply.seq = 1
    msg = Frame(Tag.MESSAGE, reply.encode_segments()).encode()
    # the parent's ack: json of a one-element list
    ack = Frame(Tag.ACK, [json.dumps([2]).encode()]).encode()
    probe = Frame(Tag.KEEPALIVE, []).encode()
    answer = Frame(Tag.KEEPALIVE_ACK, []).encode()
    alone = _state(_fed([bytes(msg), bytes(ack), bytes(probe),
                         bytes(answer)]))
    grouped = _state(_fed([bytes(msg) + bytes(ack) + bytes(probe)
                           + bytes(answer)]))
    assert alone == grouped == (
        [3], 1, [("keepalive_ack", None)],
        [("MPingReply", 1, {"i": 7}, b"payload")])


def _onwires(mode) -> tuple:
    """(the sender's transform, the receiver's) of a session in `mode`;
    (None, None) in plain crc mode."""
    if not mode:
        return None, None
    kw = dict(compress=bool(mode.get("compress")),
              secret=KEY if mode.get("secure") else None,
              nonces=("a" * 32, "b" * 32))
    return Onwire(role="cli", **kw), Onwire(role="srv", **kw)


@pytest.mark.parametrize("carrier", [
    "delivered", "a_replayed_duplicate", "dropped_by_faultinject",
    "delayed_by_faultinject", "a_batch_envelope"])
def test_a_header_ack_trims_sent_whatever_becomes_of_its_carrier(
        mode, carrier):
    """An ack that arrived is an ack: the dup filter, an injected drop
    or delay, and the envelope's unpacking all come after it."""
    if carrier == "a_batch_envelope":
        inner = [MOSDECSubOpWriteReply({"i": i}) for i in (0, 1)]
        for seq, m in enumerate(inner, 1):
            m.seq = seq
        msg = pack_batch(inner)
    else:
        msg = MPingReply({"i": 7}, b"payload")
        msg.seq = 4 if carrier == "a_replayed_duplicate" else 1
    blob = bytes(Frame(Tag.MESSAGE, msg.encode_segments(2)).encode())
    tx, rx = _onwires(mode)
    action = {"dropped_by_faultinject": "drop",
              "delayed_by_faultinject": "delay"}.get(carrier)
    try:
        if action:
            faultinject.reset(seed=1)
            faultinject.set_enabled(True)
            faultinject.arm_oneshot(entity="osd.9", msg_type="MPingReply",
                                    action=action, delay_ms=60_000)
        conn = _fed([tx.wrap(blob) if tx else blob], rx,
                    in_seq=5 if carrier == "a_replayed_duplicate" else 0)
    finally:
        faultinject.set_enabled(False)
        faultinject.reset()
    sent, in_seq, _out, queued = _state(conn)
    assert sent == [3]
    assert (in_seq, [q[:2] for q in queued]) == {
        "delivered": (1, [("MPingReply", 1)]),
        "a_replayed_duplicate": (5, []),
        "dropped_by_faultinject": (1, []),
        "delayed_by_faultinject": (1, []),
        "a_batch_envelope": (2, [("MOSDECSubOpWriteReply", 1),
                                 ("MOSDECSubOpWriteReply", 2)]),
    }[carrier]


@pytest.mark.parametrize("header,ack", [
    ({"type": MPingReply.TYPE, "seq": 1}, 0),           # the parent's
    ({"type": MPingReply.TYPE, "seq": 1, "ack": 2}, 2),
    ({"type": MPingReply.TYPE, "seq": 1, "ack": 3, "later": [1]}, 3),
    ({"later": {}, "seq": 1, "type": MPingReply.TYPE}, 0),
], ids=["no_ack_key", "ack", "ack_and_an_unknown_key", "an_unknown_key"])
def test_a_header_of_another_version_decodes(header, ack):
    """A header without the key reads as no ack (a peer that frames
    every ack); a key this version does not know is ignored."""
    segments = [json.dumps(header).encode(), b'{"i":7}', b"payload"]
    msg = Message.decode_segments(segments)
    assert (type(msg), msg.seq, msg.ack, msg.payload, msg.data) == (
        MPingReply, 1, ack, {"i": 7}, b"payload")
    conn = _fed([bytes(Frame(Tag.MESSAGE, segments).encode())])
    assert _state(conn)[:2] == ([m for m in (1, 2, 3) if m > ack], 1)


def test_with_nothing_owed_the_header_is_the_parents_byte_for_byte():
    msg = MPing({"i": 1})
    msg.seq = 9
    want = json.dumps({"type": MPing.TYPE, "seq": 9},
                      separators=(",", ":")).encode()
    assert msg.encode_segments()[0] == msg.encode_segments(0)[0] == want
    assert json.loads(msg.encode_segments(8)[0]) == {
        "type": MPing.TYPE, "seq": 9, "ack": 8}
    assert msg.ack == 0


@pytest.mark.parametrize("seq", [1, 16, 2 ** 31 + 7])
def test_the_ack_frames_bytes_are_the_parents(seq):
    """Byte for byte: `Frame(ACK, [json.dumps([seq])]).encode()`."""
    sent: list = []

    class _Writer:
        def writelines(self, parts):
            sent.append([bytes(p) for p in parts])

        async def drain(self):
            raise ConnectionResetError

    async def main():
        conn = Connection(Messenger("osd.9"), None, Policy.lossless_peer(),
                          initiator=False)
        conn._processed_seq = seq
        conn._out.put_nowait(("keepalive", None))
        with pytest.raises(ConnectionResetError):
            await conn._write_loop(_Writer())
        return conn

    conn = run(main())
    want = [bytes(Frame(Tag.KEEPALIVE, []).encode()),
            bytes(Frame(Tag.ACK, [json.dumps([seq]).encode()]).encode())]
    assert sent == [want]
    assert conn._last_acked_in == seq


def test_a_sub_op_and_its_small_neighbours_share_a_send(monkeypatch):
    """The batcher's envelope and the gather compose: a run of
    batchable sub-ops becomes ONE message frame, and it shares the send
    with the frames queued before and after it."""
    async def main():
        tap = Tap(monkeypatch)
        col = Collector()
        server, client, conn, _s = await _pair({}, col)
        datas = [os.urandom(1000 + i) for i in range(3)]
        conn.send_message(MPing({"i": 0}))
        for i, d in enumerate(datas):
            conn.send_message(MOSDECSubOpWrite({"i": i}, d))
        conn.send_message(MPing({"i": 9}))
        await _wait_for(col, 5)
        out = tap.tags(conn), [type(m).__name__ for m in col.messages]
        await _down(client, server)
        return out

    sends, names = run(main())
    assert names == ["MPing"] + ["MOSDECSubOpWrite"] * 3 + ["MPing"]
    # ping, one envelope of three, ping: three frames, one send
    assert sends == [[Tag.MESSAGE] * 3]
