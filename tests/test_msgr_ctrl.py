"""The messenger's control frames (ACK, KEEPALIVE, KEEPALIVE_ACK): a
keepalive only from an end that has received nothing for an interval,
the ack the peer is owed in the same send as whatever else is going to
it, one `writelines` for all a write-loop wake-up has to send — and the
bounds those frames exist for (`KEEPALIVE_TIMEOUT`, `IDLE_ACK_S`,
`ACK_EVERY`, replay exactly once) held as before, on plain, secure and
compressed sessions. The wire is what it was: the same frames, grouped."""
from __future__ import annotations

import asyncio
import json
import os
import time

import pytest

from ceph_tpu.msg.frames import Frame, Tag
from ceph_tpu.msg.messages import MOSDECSubOpWrite, MPing, MPingReply
from ceph_tpu.msg.messenger import (Connection, Dispatcher, Messenger,
                                    Policy, msgr_perf)
from ceph_tpu.msg.transport import SPILL_SIZE, Endpoint

from tests.test_msg import Collector, Echo, run
from tests.test_msg_transport import _wait_for

KEY = b"0123456789abcdef"
MODES = {"crc": {}, "secure": {"auth_key": KEY, "secure": True},
         "compressed": {"compress": True}}
COUNTERS = ("ctrl_frames_tx", "ctrl_rode_tx", "tx_sends",
            "keepalives_skipped")


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

        def _frame_into(conn, parts, frame, onwire):
            framed.setdefault(id(parts), [conn]).append(frame)
            return real_frame(conn, parts, frame, onwire)

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
        return [json.loads(bytes(f.segments[0]))[0]
                for c, frames, _p in self.sends if c is conn
                for f in frames if f.tag == Tag.ACK]


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
    # what control frames there were are acks, and most rode
    assert d["ctrl_frames_tx"] == tap.count(Tag.ACK)
    assert d["ctrl_rode_tx"] >= 21


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

def test_a_reply_carries_the_ack_in_the_same_send(mode, monkeypatch):
    """The reply a handler sends and the ack of the request it answers
    leave in one `writelines`; the requester's replay queue is empty a
    round trip later, long before the idle flush could have run."""
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
        out = tap.tags(sconn), d, took, sconn._ack_timer
        await _down(client, server)
        return out

    sends, d, took, timer = run(main())
    assert sends == [[Tag.MESSAGE, Tag.ACK]]
    assert d["ctrl_rode_tx"] == 1 and d["ctrl_frames_tx"] == 1
    assert d["tx_sends"] == 2           # the request, the reply
    assert took < 1.0 and timer is None     # the ride disarmed the flush


def test_with_nothing_to_ride_the_ack_leaves_within_idle_ack_s(
        mode, monkeypatch):
    """IDLE_ACK_S's bound, as before: no reply, no ride; the ack goes
    alone when the flush timer fires."""
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
    assert d["ctrl_frames_tx"] == 1 and d["ctrl_rode_tx"] == 0


def test_sixteen_unacked_messages_force_an_ack_out(mode, monkeypatch):
    """ACK_EVERY's bound, as before: no ride and a flush timer far
    away, and the sixteenth finished message still sends the ack."""
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
    acks past it; every ACK frame tells the peer something new, and a
    queued ack with nothing left to say sends nothing at all."""
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
        out = tap.acked(sconn), len(tap.tags(sconn)) - sends
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
        assert len(conn._sent) == 1 and not tap.acked(sconn)
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
    assert d["ctrl_rode_tx"] >= 1
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


def test_a_lossy_session_rides_its_acks_and_never_probes(monkeypatch):
    monkeypatch.setattr(Connection, "KEEPALIVE_INTERVAL", 0.2)

    async def main():
        tap = Tap(monkeypatch)
        server, client, conn, sconn = await _pair(
            {}, Echo(), Policy.lossy_client())
        replies = Collector()
        client.add_dispatcher(replies)
        conn.send_message(MPing({"i": 0}))
        await _wait_for(replies, 1)
        await asyncio.sleep(0.7)
        out = tap.tags(sconn), tap.count(Tag.KEEPALIVE)
        await _down(client, server)
        return out

    sends, probes = run(main())
    assert sends[0] == [Tag.MESSAGE, Tag.ACK] and probes == 0


# -- the wire is what it was ----------------------------------------------------

def _fed(chunks) -> Connection:
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
        reader = asyncio.StreamReader()
        for c in chunks:
            reader.feed_data(c)
        reader.feed_eof()
        with pytest.raises(asyncio.IncompleteReadError):
            await conn._read_loop(reader)
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
