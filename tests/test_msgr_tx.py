"""The messenger's send side: frames at or over the spill size leave by
reference (`Frame.encode_parts` into the transport's scatter `sendmsg`,
or, from `rxworker.LINE` up, the same parts handed to the send worker),
smaller ones and every frame of an onwire session through a packed blob;
a partial send keeps views of the same objects; a lossless session that
is reset in the middle of a 4 MiB frame replays the same bytes, whoever
was sending it; and the `tx_direct_bytes` / `tx_copied_bytes` /
`tx_worker_*` counters say which way it went."""
from __future__ import annotations

import asyncio
import os
import random
import socket

import pytest

from ceph_tpu.msg import frames, rxworker
from ceph_tpu.msg.frames import Frame, Tag
from ceph_tpu.msg.messages import MOSDECSubOpWrite, pack_batch
from ceph_tpu.msg.messenger import Messenger, Policy, msgr_perf
from ceph_tpu.msg.transport import SPILL_SIZE, Endpoint

from tests.test_msg import Collector
from tests.test_msg_transport import (_Relay, _slow_wire, _wait_for,  # noqa: F401
                                      _with_native, codec, run)

TX = ("tx_direct_bytes", "tx_copied_bytes", "tx_worker_bytes",
      "tx_worker_bodies", "tx_worker_cancelled", "tx_worker_declined")


@pytest.fixture(params=["worker", "transport"])
def tx(request, monkeypatch):
    """Who sends a frame of `rxworker.LINE` or more: the send worker,
    or, with the native library made unavailable, the transport, as it
    sends every frame under the line."""
    _with_native(monkeypatch, request.param == "worker")
    return request.param


def _tx() -> dict:
    d = msgr_perf().dump()
    return {k: d[k] for k in TX}


def _delta(before: dict) -> dict:
    return {k: v - before[k] for k, v in _tx().items()}


async def _small_sndbuf_pair():
    """(sending endpoint, receiving endpoint) on a socketpair whose send
    buffer is as small as the kernel allows: a 4 MiB frame goes out in
    many partial sends."""
    loop = asyncio.get_running_loop()
    a, b = socket.socketpair()
    a.setsockopt(socket.SOL_SOCKET, socket.SO_SNDBUF, 4096)
    b.setsockopt(socket.SOL_SOCKET, socket.SO_RCVBUF, 4096)
    _, tx = await loop.create_connection(lambda: Endpoint(msgr_perf()),
                                         sock=a)
    _, rx = await loop.create_connection(lambda: Endpoint(msgr_perf()),
                                         sock=b)
    return tx, rx


_FRAMES = {
    "4m_message": lambda rng: [b'{"type":112,"seq":1}', b'{"i":0}',
                               rng.randbytes(4 << 20)],
    "scatter_envelope": lambda rng: [
        b'{"type":115,"seq":2}', b'{"msgs":[]}',
        [rng.randbytes(512 << 10), memoryview(rng.randbytes(512 << 10)),
         b"", bytearray(rng.randbytes(300))]],
    "at_the_line": lambda rng: [b"h", b"p", rng.randbytes(SPILL_SIZE - 2)],
    "four_segments": lambda rng: [rng.randbytes(100000), b"",
                                  rng.randbytes(70000), b"\x7c\xec" * 9],
}


@pytest.mark.parametrize("shape", list(_FRAMES))
def test_partial_sends_keep_views_and_frame_read_takes_them(codec, shape):
    """`Frame.read` at the far end of a socket that takes a few KiB a
    send: what the transport queues after the first `sendmsg` is views
    of the frame's own parts, nothing joined, and the frame arrives
    whole."""
    async def main():
        tx, rx = await _small_sndbuf_pair()
        segs = _FRAMES[shape](random.Random(5))
        f = Frame(Tag.MESSAGE, segs)
        parts = f.encode_parts()
        owners = {id(p.obj if isinstance(p, memoryview) else p)
                  for p in parts}
        reading = asyncio.create_task(Frame.read(rx))
        tx.writelines(parts)
        # the socket took a few KiB; the rest waits as views
        queued = list(tx.transport._buffer)
        assert tx.transport.get_write_buffer_size() > f.payload_len() // 2
        assert all(type(q) is memoryview and id(q.obj) in owners
                   for q in queued)
        assert len(queued) <= len(parts)
        await tx.drain()
        got = await asyncio.wait_for(reading, 30)
        want = [b"".join(bytes(p) for p in s) if isinstance(s, list)
                else bytes(s) for s in segs]
        assert got.tag == Tag.MESSAGE
        assert [bytes(s) for s in got.segments] == want
        for ep in (tx, rx):
            ep.close()
            await ep.wait_closed()

    run(main())


def test_nothing_is_queued_on_a_transport_that_is_lost():
    """asyncio's `writelines` queues on an aborted transport and
    registers its dead socket for writing (its `write` does not); the
    endpoint drops the parts and the next `drain()` raises."""
    async def main():
        tx, rx = await _small_sndbuf_pair()
        loop = asyncio.get_running_loop()
        fd = tx.transport.get_extra_info("socket").fileno()
        tx.transport.abort()
        tx.writelines([b"x" * 100000, b"y" * 100000])
        assert tx.transport.get_write_buffer_size() == 0
        key = loop._selector.get_map().get(fd)
        assert key is None or not key.events & 2      # no writer left
        with pytest.raises(ConnectionError):
            await tx.drain()
        rx.close()
        await rx.wait_closed()

    run(main())


def _capture_writes(monkeypatch) -> list:
    """Every list the write loops hand to `Endpoint.writelines`, and the
    parts of every frame the send worker took (`Endpoint.send_frame`)."""
    seen: list = []
    real, real_frame = Endpoint.writelines, Endpoint.send_frame

    def writelines(self, parts):
        parts = list(parts)
        seen.append(parts)
        real(self, parts)

    def send_frame(self, head, frame):
        job = real_frame(self, head, frame)
        if job is not None:
            seen.append([p for seg in frame.segments for p in
                         (seg if isinstance(seg, (list, tuple)) else [seg])])
        return job

    monkeypatch.setattr(Endpoint, "writelines", writelines)
    monkeypatch.setattr(Endpoint, "send_frame", send_frame)
    return seen


@pytest.mark.parametrize("n", [0, 1000, SPILL_SIZE - 1, SPILL_SIZE,
                               SPILL_SIZE + 1, 4 << 20],
                         ids=lambda n: f"{n}B")
def test_the_payload_object_is_the_one_on_the_wire(codec, tx, monkeypatch,
                                                   n):
    """A message's data goes to the transport, or to the send worker, as
    the object the sender handed in when its frame is at or over the
    line (counted in `tx_direct_bytes`), and inside one packed blob
    under it (counted in `tx_copied_bytes`); the line is on the frame's
    payload, header and JSON segments included."""
    async def main():
        server = Messenger("osd.1")
        col = Collector()
        server.add_dispatcher(col)
        addr = await server.bind()
        client = Messenger("osd.2")
        conn = await client.connect(addr, Policy.lossless_peer())
        payload = os.urandom(n)
        msg = MOSDECSubOpWrite({"i": 0}, payload)
        seen = _capture_writes(monkeypatch)
        before = _tx()
        conn.send_message(msg)
        await _wait_for(col, 1)
        d = _delta(before)
        assert col.messages[0].data == payload
        await client.shutdown()
        await server.shutdown()
        return d, seen, payload, sum(
            len(s) for s in msg.encode_segments())

    d, seen, payload, framed = run(main())
    carrying = [parts for parts in seen
                if any(p is payload for p in parts)]
    if framed >= SPILL_SIZE:
        assert len(carrying) == 1
        assert d["tx_direct_bytes"] == framed
        # the acks and keepalives of the pair are all that was copied
        assert d["tx_copied_bytes"] < 1000
        worked = tx == "worker" and framed >= rxworker.LINE
        assert d["tx_worker_bytes"] == (framed if worked else 0)
        assert d["tx_worker_bodies"] == (1 if worked else 0)
    else:
        assert not carrying and all(len(parts) == 1 for parts in seen)
        assert d["tx_direct_bytes"] == 0
        assert d["tx_copied_bytes"] >= framed


def test_a_batch_envelopes_scatter_parts_go_by_reference(codec, tx,
                                                         monkeypatch):
    """Sub-op writes that pile up for one peer leave as one envelope
    whose data segment is a scatter list: every inner message's data is
    still the sender's object on the way to `sendmsg`, the transport's
    or the send worker's."""
    async def main():
        server = Messenger("osd.1")
        col = Collector()
        server.add_dispatcher(col)
        addr = await server.bind()
        client = Messenger("osd.2")
        conn = await client.connect(addr, Policy.lossless_peer())
        datas = [os.urandom(200000 + i) for i in range(4)]
        seen = _capture_writes(monkeypatch)
        before = _tx()
        for i, d in enumerate(datas):
            conn.send_message(MOSDECSubOpWrite({"i": i}, d))
        await _wait_for(col, 4)
        d = _delta(before)
        assert [bytes(m.data) for m in col.messages] == datas
        await client.shutdown()
        await server.shutdown()
        return d, seen, datas

    d, seen, datas = run(main())
    flat = [p for parts in seen for p in parts]
    assert all(any(p is data for p in flat) for data in datas)
    # one frame carried all four (the write loop coalesced them)
    assert any(sum(any(p is data for p in parts) for data in datas) > 1
               for parts in seen)
    assert d["tx_direct_bytes"] >= sum(map(len, datas))
    assert d["tx_copied_bytes"] < 1000
    # the envelope of four (800 kB) is over the worker's line
    assert (d["tx_worker_bodies"] >= 1) == (tx == "worker")
    assert d["tx_worker_declined"] == 0


def test_pack_batch_frames_by_reference():
    msgs = [MOSDECSubOpWrite({"i": i}, os.urandom(40000)) for i in range(3)]
    for i, m in enumerate(msgs):
        m.seq = i + 1
    env = pack_batch(msgs)
    parts = Frame(Tag.MESSAGE, env.encode_segments()).encode_parts()
    assert all(any(p is m.data for p in parts) for m in msgs)


@pytest.mark.parametrize("mode", [{"compress": True}], ids=["compressed"])
def test_an_onwire_session_keeps_the_packed_blob(codec, mode):
    async def main():
        server = Messenger("osd.1", **mode)
        col = Collector()
        server.add_dispatcher(col)
        addr = await server.bind()
        client = Messenger("osd.2", **mode)
        conn = await client.connect(addr, Policy.lossless_peer())
        payload = os.urandom(1 << 20)
        before = _tx()
        conn.send_message(MOSDECSubOpWrite({"i": 0}, payload))
        await _wait_for(col, 1)
        d = _delta(before)
        assert col.messages[0].data == payload
        await client.shutdown()
        await server.shutdown()
        return d

    d = run(main())
    assert d["tx_direct_bytes"] == 0
    assert d["tx_copied_bytes"] >= 1 << 20


def test_a_reset_in_the_middle_of_a_4mib_frame_replays_the_same_bytes(
        codec, tx, monkeypatch):
    """Yank the wire while 4 MiB frames are half sent: the session
    reconnects and frames the same message objects again, by reference
    again, and every message arrives once, in order, byte for byte. A
    frame the send worker was in the middle of is taken back from it
    (`tx_worker_cancelled`) before the session sees the fault."""
    N = 6

    async def main():
        server = Messenger("osd.1")
        col = Collector()
        server.add_dispatcher(col)
        addr = await server.bind()
        relay = _Relay(addr)
        client = Messenger("osd.2")
        conn = await client.connect(await relay.start(),
                                    Policy.lossless_peer())
        rng = random.Random(11)
        datas = [rng.randbytes((4 << 20) + i) for i in range(N)]
        seen = _capture_writes(monkeypatch)
        before = _tx()
        aborts = 0

        async def yank():
            nonlocal aborts
            while aborts < 2:
                await asyncio.sleep(0)
                _slow_wire(conn)
                for c in list(server._sessions.values()):
                    ep = c._reader
                    if ep is None or ep.body_filled() < 1 << 20:
                        continue
                    aborts += 1
                    conn._writer.transport.abort()
                    await asyncio.sleep(0.05)

        yanker = asyncio.create_task(yank())
        for i, d in enumerate(datas):
            conn.send_message(MOSDECSubOpWrite({"i": i}, d))
        await _wait_for(col, N)
        await asyncio.wait_for(yanker, 30)
        d = _delta(before)
        assert [m.payload["i"] for m in col.messages] == list(range(N))
        assert all(m.data == want for m, want in zip(col.messages, datas))
        await client.shutdown()
        await server.shutdown()
        await relay.stop()
        return d, seen, datas

    d, seen, datas = run(main(), timeout=90)
    sends = [sum(any(p is data for p in parts) for parts in seen)
             for data in datas]
    # every message went by reference, and the yanked ones twice or more
    assert all(n >= 1 for n in sends) and sum(sends) >= N + 2
    assert d["tx_direct_bytes"] >= sum(map(len, datas)) + (8 << 20)
    if tx == "worker":
        # the first yank found a frame in the worker's hands; the second
        # that, or one the transport was sending (an ack stuck in its
        # queue in front of the frame: declined)
        assert 1 <= d["tx_worker_cancelled"] <= 2
        assert d["tx_worker_bodies"] + d["tx_worker_declined"] >= N
        assert d["tx_worker_bodies"] >= 1
    else:
        assert d["tx_worker_bodies"] == d["tx_worker_cancelled"] == 0
    assert not rxworker._jobs and not rxworker.running()


def test_the_codec_is_logged_once_at_messenger_start():
    from ceph_tpu.msg import messenger
    from ceph_tpu.native import ec_native
    from ceph_tpu.utils.dout import get_logger

    messenger._log_codec.cache_clear()
    Messenger("osd.7")
    Messenger("osd.8")
    lines = [ln for ln in get_logger().dump_recent()
             if "crc32c kernel" in ln]
    assert len(lines) >= 1 and messenger._log_codec.cache_info().misses == 1
    assert f"crc32c kernel {ec_native.crc32c_impl()}" in lines[-1]
    assert ("native" if frames.native_active() else "python") in lines[-1]
