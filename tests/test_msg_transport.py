"""The messenger's own socket endpoint (msg/transport.py): frames fed in
every chunking decode to what `Frame.decode` gives, a body lands in its
own buffer, EOF and abort raise in the pending read, a full spill pauses
reading, and the sessions built on it keep their guarantees."""
from __future__ import annotations

import asyncio
import os
import random
import socket
import time
import zlib

import pytest

from ceph_tpu.msg import frames
from ceph_tpu.msg.frames import (Frame, FrameError, Onwire, Tag,
                                 encode_trace_ctx)
from ceph_tpu.msg.messages import MOSDECSubOpWrite, MPing
from ceph_tpu.msg.messenger import Messenger, Policy, msgr_perf
from ceph_tpu.msg import rxworker, transport
from ceph_tpu.msg.transport import NARROW, SPILL_SIZE, Endpoint

from tests.test_msg import Collector

try:
    import cryptography  # noqa: F401
    _HAVE_CRYPTO = True
except ImportError:
    _HAVE_CRYPTO = False


def run(coro, timeout=60):
    return asyncio.run(asyncio.wait_for(coro, timeout))


@pytest.fixture(params=[True, False], ids=["native", "python"])
def codec(request):
    """Both frame codecs: what `CEPH_TPU_FRAME_NATIVE=0` selects at
    import is what `set_native(False)` selects here."""
    was = frames.native_active()
    if frames.set_native(request.param) != request.param:
        pytest.skip("the native codec is not built here")
    yield request.param
    frames.set_native(was)


# -- an endpoint driven by hand, as the selector transport drives it ----------

class _FakeTransport:
    def __init__(self):
        self.paused = False
        self.closing = False

    def pause_reading(self):
        self.paused = True

    def resume_reading(self):
        self.paused = False

    def is_closing(self):
        return self.closing

    def close(self):
        self.closing = True

    def get_extra_info(self, name, default=None):
        return default      # no socket: nothing the receive worker can have


class _Perf:
    def __init__(self):
        self.v = {"rx_direct_bytes": 0, "rx_spill_bytes": 0, "rx_recvs": 0}

    def inc(self, key, amount=1):
        self.v[key] += amount


async def _made():
    ep = Endpoint(_Perf())
    ep.connection_made(_FakeTransport())
    return ep


async def _feed(ep: Endpoint, wire: bytes, chunks: list[int]) -> None:
    """What `_read_ready__get_buffer` does, `chunks[i]` bytes a recv at
    most (the last size repeats), yielding while reading is paused and
    after every recv so the reader task takes its turn."""
    view = memoryview(wire)
    off = 0
    i = 0
    while off < len(wire):
        while ep.transport.paused:
            await asyncio.sleep(0)
        buf = ep.get_buffer(-1)
        assert len(buf) > 0, "get_buffer() returned an empty view"
        k = min(len(buf), chunks[min(i, len(chunks) - 1)], len(wire) - off)
        buf[:k] = view[off:off + k]
        ep.buffer_updated(k)
        off += k
        i += 1
        await asyncio.sleep(0)


def _random_frame(rng: random.Random, size: str) -> Frame:
    nseg = rng.randint(0, 4)
    segs = []
    for j in range(nseg):
        if size == "control":
            n = rng.choice([0, 1, 17, 200, 3000])
        elif j == 2 or nseg < 3 and j == nseg - 1:
            n = {"512k": 512 << 10, "4m": 4 << 20}[size] + rng.randint(0, 9)
        else:
            n = rng.randint(0, 64)
        segs.append(rng.randbytes(n))
    if nseg == 4 and rng.random() < 0.5:
        segs[3] = encode_trace_ctx({"t": rng.getrandbits(64),
                                    "s": rng.getrandbits(64), "f": 1})
    return Frame(rng.choice([Tag.MESSAGE, Tag.ACK, Tag.KEEPALIVE]), segs)


def _chunkings(rng: random.Random, wire: bytes, first: Frame) -> dict:
    pre = 4 + 4 * len(first.segments) + 4
    return {
        "byte_at_a_time_then_whole": [1] * min(len(wire), 300) + [1 << 30],
        "split_inside_preamble": [2, 5, 1 << 30],
        "split_inside_crc": [pre - 2, 3, 1 << 30],
        "all_in_one_chunk": [1 << 30],
        "head_in_spill": [pre + 1000, 1 << 30],
        "random": [rng.choice([1, 3, 7, 100, NARROW, SPILL_SIZE, 200000])
                   for _ in range(400)] + [1 << 30],
    }


_CHUNKINGS = ["byte_at_a_time_then_whole", "split_inside_preamble",
              "split_inside_crc", "all_in_one_chunk", "head_in_spill",
              "random"]


@pytest.mark.parametrize("chunking", _CHUNKINGS)
@pytest.mark.parametrize("mix", ["control", "512k", "4m", "mixed"])
def test_frames_in_any_chunking_decode_as_frame_decode(codec, mix,
                                                       chunking):
    seed = zlib.crc32(f"{mix}/{chunking}".encode())
    rng = random.Random(seed)
    sizes = {"control": ["control"] * 12, "512k": ["512k"] * 3,
             "4m": ["4m", "4m"],
             "mixed": ["control", "4m", "control", "control", "512k",
                       "512k", "control"]}[mix]
    sent = [_random_frame(rng, s) for s in sizes]
    blobs = [bytes(f.encode()) for f in sent]
    wire = b"".join(blobs)
    want = [Frame.decode(b) for b in blobs]
    chunks = _chunkings(rng, wire, sent[0])[chunking]

    async def main():
        ep = await _made()
        feeder = asyncio.create_task(_feed(ep, wire, chunks))
        got = [await Frame.read(ep) for _ in sent]
        await feeder
        return ep, got

    ep, got = run(main())
    for g, w in zip(got, want):
        assert g.tag == w.tag
        assert [bytes(s) for s in g.segments] == \
            [bytes(s) for s in w.segments]
        # what the store takes by reference: views nobody can write to
        assert all(isinstance(s, memoryview) and s.readonly
                   for s in g.segments)
    v = ep._perf.v
    assert v["rx_direct_bytes"] + v["rx_spill_bytes"] == len(wire)
    assert ep._wpos == ep._rpos == 0 and ep._dest is None


def test_corrupt_body_is_refused_before_dispatch(codec):
    blob = bytearray(Frame(Tag.MESSAGE,
                           [b"h", b"p", os.urandom(300000)]).encode())
    blob[-100] ^= 0x01

    async def main():
        ep = await _made()
        feeder = asyncio.create_task(_feed(ep, bytes(blob), [1 << 30]))
        with pytest.raises(FrameError, match="crc"):
            await Frame.read(ep)
        await feeder

    run(main())


@pytest.mark.parametrize("n", [0, 1, NARROW, SPILL_SIZE, SPILL_SIZE + 1,
                               1 << 20])
def test_read_goes_direct_only_above_the_spill(n):
    data = os.urandom(n)

    async def main():
        ep = await _made()
        feeder = asyncio.create_task(_feed(ep, b"ab" + data, [2, 1 << 30]))
        assert await ep.readexactly(2) == b"ab"
        out = await ep.readexactly(n)
        await feeder
        return ep, out

    ep, out = run(main())
    assert out == data
    assert (type(out) is bytearray) == (n > SPILL_SIZE)
    assert ep._perf.v["rx_direct_bytes"] == (n if n > SPILL_SIZE else 0)


def test_window_narrows_after_a_body_and_widens_on_small_traffic():
    async def main():
        ep = await _made()
        assert len(ep.get_buffer(-1)) == NARROW          # a new session
        body = os.urandom(SPILL_SIZE + 5)
        feeder = asyncio.create_task(_feed(ep, body, [1 << 30]))
        assert await ep.readexactly(len(body)) == body
        await feeder
        assert len(ep.get_buffer(-1)) == NARROW
        small = os.urandom(3 * NARROW)
        feeder = asyncio.create_task(_feed(ep, small, [1 << 30]))
        for i in range(0, len(small), 512):
            assert await ep.readexactly(512) == small[i:i + 512]
        await feeder
        assert len(ep.get_buffer(-1)) == SPILL_SIZE

    run(main())


# -- a body's memory: a `bytearray` at its length, not zero-filled ----------------

#: how a body gets its memory: the C API's unzeroed `bytearray`, and
#: `bytearray(n)`, which an interpreter without that symbol falls back to
HOW = ["unzeroed", "zeroed"]
BODY_SIZES = [SPILL_SIZE + 1, SPILL_SIZE + 4096, 100_003, 777_777, 512 << 10,
              (512 << 10) + 2070, 4 << 20, (4 << 20) + 1234]


@pytest.fixture
def how(request, monkeypatch):
    if request.param == "zeroed":
        monkeypatch.setattr(transport, "_new_body", bytearray)
    else:
        assert transport._new_body is not bytearray, \
            "this interpreter has the C API: the binding should have held"
    return request.param


@pytest.mark.parametrize("how", HOW, indirect=True)
@pytest.mark.parametrize("head", [0, 1000])
@pytest.mark.parametrize("n", BODY_SIZES)
def test_a_body_is_the_senders_bytes_however_its_memory_came(n, head, how):
    """Exactly `n` bytes, a plain `bytearray`, equal to what was sent:
    with `head` bytes of it already in the spill behind the two that
    were read first, and twice, so that the second body may get the
    first one's freed memory."""
    data = [os.urandom(n), os.urandom(n)]

    async def main():
        ep = await _made()
        wire = b"ab" + data[0] + b"cd" + data[1]
        feeder = asyncio.create_task(
            _feed(ep, wire, [2 + head, n - head, 2 + head, 1 << 30]))
        out = []
        for i, lead in enumerate((b"ab", b"cd")):
            assert await ep.readexactly(2) == lead
            got = await ep.readexactly(n)
            assert type(got) is bytearray and len(got) == n
            assert got == data[i]
            out.append(len(got))
            del got
        await feeder
        return ep, out

    ep, out = run(main())
    assert out == [n, n]
    assert ep._perf.v["rx_direct_bytes"] == 2 * (n - head)
    assert ep._perf.v["rx_spill_bytes"] == 2 * (2 + head)


@pytest.mark.parametrize("how", HOW, indirect=True)
@pytest.mark.parametrize("n,got", [(SPILL_SIZE + 1, 1), (512 << 10, 300_001),
                                   (4 << 20, (4 << 20) - 1)])
def test_a_body_cut_short_hands_back_what_came_and_no_byte_more(n, got, how):
    """EOF short of `n`: the partial read is the bytes received, never
    the tail of the buffer, which nobody has written."""
    data = os.urandom(got)

    async def main():
        ep = await _made()
        read = asyncio.create_task(ep.readexactly(n))
        await _feed(ep, data, [1000, 1 << 30])
        assert not read.done()
        ep.eof_received()
        with pytest.raises(asyncio.IncompleteReadError) as ei:
            await read
        return ei.value

    err = run(main())
    assert err.expected == n
    assert type(err.partial) is bytes and err.partial == data


@pytest.mark.parametrize("how", HOW, indirect=True)
def test_a_body_carries_no_export_and_the_store_adopts_its_window(how):
    """Nothing is left on the buffer once it is full: its owner can
    resize it, and MemStore keeps the read-only window it is handed as
    it keeps one on a `bytearray(n)`."""
    from ceph_tpu.objectstore import (CollectionId, Ghobject, MemStore,
                                      Transaction)
    from ceph_tpu.utils import copytrack

    n, slack = (512 << 10) + 2070, 2070
    data = os.urandom(n)

    async def main():
        ep = await _made()
        feeder = asyncio.create_task(_feed(ep, data, [1 << 30]))
        out = await ep.readexactly(n)
        await feeder
        return out

    body = run(main())
    view = memoryview(body).toreadonly()[slack:]
    store = MemStore()
    store.mkfs()
    store.mount()
    cid, oid = CollectionId.make_pg(1, 0x2A), Ghobject(pool=1, name="o")
    store.queue_transaction(Transaction().create_collection(cid))
    before = dict(copytrack.snapshot()["stages"]["store_write"])
    store.queue_transaction(Transaction().write(cid, oid, 0, view))
    after = copytrack.snapshot()["stages"]["store_write"]
    assert store._colls[cid][oid].data is view
    assert (after["referenced_bytes"] - before["referenced_bytes"],
            after["copied_bytes"] - before["copied_bytes"]) == (n - slack, 0)
    assert store.read(cid, oid) == data[slack:]
    store.umount()
    with pytest.raises(BufferError):        # the window's, and only its
        body.extend(b"x")
    del view, store, after
    body.extend(b"x")
    assert body == data + b"x"


@pytest.mark.parametrize("n", BODY_SIZES)
def test_an_unzeroed_body_is_a_plain_bytearray_of_its_length(n):
    buf = transport._unzeroed()(n)
    assert type(buf) is bytearray and len(buf) == n
    buf[:] = bytes(n)               # every byte is there to be written
    buf.extend(b"x")                # and its owner may resize it
    assert buf == bytes(n) + b"x"


@pytest.mark.parametrize("broken", ["no_ctypes", "no_pythonapi", "no_symbol"])
def test_without_the_c_api_a_body_is_bytearray_n(broken, monkeypatch):
    """Found once, at import, by trying it: an interpreter without
    `ctypes`, without `ctypes.pythonapi` or without the symbol gets
    the `bytearray(n)` the transport had before, and still imports."""
    import ctypes.util
    import sys

    if broken == "no_ctypes":
        monkeypatch.setitem(sys.modules, "ctypes", None)
    elif broken == "no_pythonapi":
        monkeypatch.delattr(ctypes, "pythonapi")
    else:
        libm = ctypes.util.find_library("m")
        if libm is None:
            pytest.skip("no second library to look the symbol up in")
        monkeypatch.setattr(ctypes, "pythonapi", ctypes.PyDLL(libm))
    assert transport._unzeroed() is bytearray


# -- real sockets ----------------------------------------------------------------

async def _socket_pair():
    """(server endpoint, client endpoint, server) over loopback."""
    loop = asyncio.get_running_loop()
    accepted = loop.create_future()
    perf = msgr_perf()
    server = await loop.create_server(
        lambda: Endpoint(perf, accepted.set_result), "127.0.0.1", 0)
    port = server.sockets[0].getsockname()[1]
    _, cli = await loop.create_connection(lambda: Endpoint(perf),
                                          "127.0.0.1", port)
    return await accepted, cli, server


async def _close(server, *eps):
    for ep in eps:
        ep.close()
        await ep.wait_closed()
    server.close()
    await server.wait_closed()


@pytest.mark.parametrize("how", ["eof", "abort", "peer_abort"])
def test_eof_or_abort_in_mid_body_raises_in_the_pending_read(how):
    async def main():
        srv, cli, server = await _socket_pair()
        n = 4 << 20
        read = asyncio.create_task(srv.readexactly(n))
        cli.write(b"x" * (n // 2))
        await cli.drain()
        while srv.body_filled() < n // 2:
            await asyncio.sleep(0.01)
            assert not read.done()
        if how == "eof":
            cli.transport.write_eof()
        elif how == "abort":
            srv.transport.abort()
        else:
            cli.transport.abort()
        with pytest.raises((asyncio.IncompleteReadError,
                            ConnectionError)) as ei:
            await asyncio.wait_for(read, 10)
        if isinstance(ei.value, asyncio.IncompleteReadError):
            assert ei.value.expected == n
            assert len(ei.value.partial) == n // 2
        # and every later read fails the same way, at once
        with pytest.raises((asyncio.IncompleteReadError, ConnectionError)):
            await srv.readexactly(4)
        await _close(server, srv, cli)

    run(main())


def test_nobody_reading_pauses_with_bounded_memory_and_resumes(codec):
    """A consumer that stops reading (a dispatcher that blocks it):
    the endpoint holds one spill of bytes and no more, pauses the
    socket, and every frame still arrives once it reads again."""
    async def main():
        srv, cli, server = await _socket_pair()
        sent = [Frame(Tag.MESSAGE, [b"h%d" % i, os.urandom(1000)])
                for i in range(2000)]
        for f in sent:
            cli.write(f.encode())
        for _ in range(200):
            await asyncio.sleep(0.005)
            if srv._reading_paused:
                break
        assert srv._reading_paused
        assert srv._wpos - srv._rpos == SPILL_SIZE == len(srv._spill_mv)
        assert srv._dest is None
        await asyncio.sleep(0.05)           # and it stays there
        assert srv._wpos - srv._rpos == SPILL_SIZE
        got = [await Frame.read(srv) for _ in sent]
        assert not srv._reading_paused
        assert [[bytes(s) for s in g.segments] for g in got] == \
            [f.segments for f in sent]
        await _close(server, srv, cli)

    run(main())


def test_drain_waits_for_the_peer_and_fails_when_the_transport_is_lost():
    async def main():
        srv, cli, server = await _socket_pair()
        blob = b"z" * (32 << 20)            # past both socket buffers
        cli.write(blob)
        drained = asyncio.create_task(cli.drain())
        await asyncio.sleep(0.05)
        assert cli._writing_paused and not drained.done()
        assert len(await srv.readexactly(len(blob))) == len(blob)
        await asyncio.wait_for(drained, 10)
        cli.write(blob)
        drained = asyncio.create_task(cli.drain())
        await asyncio.sleep(0.05)
        cli.transport.abort()
        with pytest.raises(ConnectionError):
            await asyncio.wait_for(drained, 10)
        with pytest.raises(ConnectionError):
            await cli.drain()
        await _close(server, srv, cli)

    run(main())


# -- sessions on top ---------------------------------------------------------------

def _slow_wire(conn) -> None:
    """A small send buffer on `conn`'s socket (on each new one after a
    reconnect: call it again): with a `_Relay` behind it, whoever sends
    a 4 MiB frame is still sending it when a quarter has arrived."""
    ep = conn._writer
    if ep is not None and not getattr(ep, "slowed", False):
        ep.get_extra_info("socket").setsockopt(
            socket.SOL_SOCKET, socket.SO_SNDBUF, 131072)
        ep.slowed = True


class _Relay:
    """A relay in front of `addr` that hands on at most 64 KiB a turn of
    the loop and holds little itself (its sockets' buffers are small),
    so that a body crosses in many pieces, a turn of the loop apart, and
    whoever sends or receives it, a transport or the worker's thread, is
    seen with it half done. A connection lost on one side is closed on
    the other."""
    BUF = 65536

    def __init__(self, addr):
        self.addr = addr
        self._tasks: set = set()

    async def start(self):
        sock = socket.socket()
        sock.setsockopt(socket.SOL_SOCKET, socket.SO_RCVBUF, self.BUF)
        sock.bind(("127.0.0.1", 0))
        self.server = await asyncio.start_server(self._serve, sock=sock)
        return self.server.sockets[0].getsockname()[:2]

    def _passing(self, data: bytes, seen: int, to_addr: bool) -> bytes:
        """`data`, `seen` bytes into its connection and direction, as it
        is handed on."""
        return data

    async def _serve(self, r, w):
        self._tasks.add(asyncio.current_task())
        ur, uw = await asyncio.open_connection(*self.addr)
        for x in (w, uw):
            x.get_extra_info("socket").setsockopt(
                socket.SOL_SOCKET, socket.SO_SNDBUF, self.BUF)

        async def pump(src, dst, to_addr):
            seen = 0
            try:
                while data := await src.read(self.BUF):
                    dst.write(self._passing(data, seen, to_addr))
                    seen += len(data)
                    await dst.drain()
                    await asyncio.sleep(0)
            except (ConnectionError, asyncio.CancelledError):
                pass
            finally:
                dst.close()

        await asyncio.gather(pump(r, uw, True), pump(ur, w, False))

    async def stop(self):
        self.server.close()
        for t in list(self._tasks):
            t.cancel()
        await asyncio.gather(*self._tasks, return_exceptions=True)


async def _wait_for(col: Collector, n: int) -> None:
    while len(col.messages) < n:
        col.got.clear()
        await asyncio.wait_for(col.got.wait(), 30)


@pytest.mark.parametrize("how", HOW, indirect=True)
def test_a_4mib_message_lands_in_its_own_buffer(codec, how):
    async def main():
        server = Messenger("osd.1")
        col = Collector()
        server.add_dispatcher(col)
        addr = await server.bind()
        client = Messenger("osd.2")
        conn = await client.connect(addr, Policy.lossless_peer())
        payload = os.urandom(4 << 20)
        before = dict(msgr_perf().dump())
        conn.send_message(MOSDECSubOpWrite({"i": 0}, payload))
        await _wait_for(col, 1)
        after = dict(msgr_perf().dump())
        msg = col.messages[0]
        assert isinstance(msg.data, memoryview) and msg.data.readonly
        assert type(msg.data.obj) is bytearray
        assert msg.data == payload
        await client.shutdown()
        await server.shutdown()
        return {k: after[k] - before[k] for k in
                ("rx_direct_bytes", "rx_spill_bytes", "rx_recvs")}

    d = run(main())
    assert d["rx_direct_bytes"] >= (4 << 20) - SPILL_SIZE
    assert d["rx_spill_bytes"] < SPILL_SIZE
    assert 2 <= d["rx_recvs"] < 256


@pytest.mark.parametrize("side", ["initiator", "acceptor", "both"])
def test_lossless_pair_survives_aborts_in_mid_body(codec, side):
    """Yank the wire while a 1 MiB body is half received, from either
    end: every message still arrives exactly once, in order, whole."""
    N = 24

    async def main():
        server = Messenger("osd.1")
        col = Collector()
        server.add_dispatcher(col)
        addr = await server.bind()
        relay = _Relay(addr)
        client = Messenger("osd.2")
        conn = await client.connect(await relay.start(),
                                    Policy.lossless_peer())
        rng = random.Random(7)
        datas = [rng.randbytes((1 << 20) + i) for i in range(N)]
        aborts = 0

        async def yank():
            nonlocal aborts
            while aborts < 3:
                await asyncio.sleep(0)
                for c in list(server._sessions.values()):
                    ep = c._reader
                    if ep is None or ep.body_filled() < 100000:
                        continue
                    aborts += 1
                    victims = {"initiator": [conn], "acceptor": [c],
                               "both": [conn, c]}[side]
                    for v in victims:
                        if v._writer is not None:
                            v._writer.transport.abort()
                    await asyncio.sleep(0.05)

        yanker = asyncio.create_task(yank())
        for i, d in enumerate(datas):
            conn.send_message(MOSDECSubOpWrite({"i": i}, d))
            if i % 6 == 0:
                await asyncio.sleep(0.02)
        await _wait_for(col, N)
        await asyncio.wait_for(yanker, 30)
        assert aborts == 3
        assert [m.payload["i"] for m in col.messages] == list(range(N))
        assert all(m.data == d for m, d in zip(col.messages, datas))
        await client.shutdown()
        await server.shutdown()
        await relay.stop()

    run(main(), timeout=90)


_MODES = [pytest.param({"compress": True}, id="compressed"),
          pytest.param({"secure": True}, id="secure", marks=pytest.mark.skipif(
              not _HAVE_CRYPTO, reason="needs 'cryptography'")),
          pytest.param({"secure": True, "compress": True},
                       id="secure_compressed", marks=pytest.mark.skipif(
              not _HAVE_CRYPTO, reason="needs 'cryptography'"))]


@pytest.mark.parametrize("mode", _MODES)
def test_onwire_modes_read_through_the_endpoint(codec, mode):
    """Secure and compressed frames are one small read and one read of
    the envelope's length: control size out of the spill, a payload
    through the direct path (compressible or not)."""
    async def main():
        key = b"k" * 16
        server = Messenger("osd.1", auth_key=key, **mode)
        col = Collector()
        server.add_dispatcher(col)
        addr = await server.bind()
        client = Messenger("osd.2", auth_key=key, **mode)
        conn = await client.connect(addr, Policy.lossless_peer())
        assert conn._onwire is not None
        datas = [b"", b"tiny", b"a" * (1 << 20), os.urandom(1 << 20),
                 os.urandom(SPILL_SIZE - 100), b"end"]
        before = dict(msgr_perf().dump())
        for i, d in enumerate(datas):
            conn.send_message(MOSDECSubOpWrite({"i": i}, d))
            conn.send_message(MPing({"i": i}))
        await _wait_for(col, 2 * len(datas))
        after = dict(msgr_perf().dump())
        got = [m for m in col.messages if isinstance(m, MOSDECSubOpWrite)]
        assert [bytes(m.data) for m in got] == datas
        assert all(isinstance(m.data, memoryview) and m.data.readonly
                   for m in got)
        assert [m.payload["i"] for m in col.messages
                if isinstance(m, MPing)] == list(range(len(datas)))
        await client.shutdown()
        await server.shutdown()
        return after["rx_direct_bytes"] - before["rx_direct_bytes"]

    # the incompressible MiB cannot have come through the spill
    assert run(main()) >= (1 << 20) - SPILL_SIZE


def test_onwire_read_frame_over_the_endpoint_in_small_chunks(codec):
    tx = Onwire(compress=True)
    rx = Onwire(compress=True)
    sent = [Frame(Tag.MESSAGE, [b"h", b"p", b"a" * 300000]),
            Frame(Tag.MESSAGE, [b"h", b"p", os.urandom(300000)]),
            Frame(Tag.ACK, [b"[1]"])]
    wire = b"".join(tx.wrap(f.encode()) for f in sent)

    async def main():
        ep = await _made()
        feeder = asyncio.create_task(_feed(ep, wire, [3, 1, 1000, 70000]))
        got = [await rx.read_frame(ep) for _ in sent]
        await feeder
        return got

    got = run(main())
    assert [[bytes(s) for s in g.segments] for g in got] == \
        [f.segments for f in sent]
    assert all(s.readonly for g in got for s in g.segments)


def test_no_stream_pair_is_left_in_the_messenger():
    import ceph_tpu.msg as pkg
    root = os.path.dirname(pkg.__file__)
    for name in os.listdir(root):
        if name.endswith(".py"):
            with open(os.path.join(root, name)) as f:
                src = f.read()
            for gone in ("open_connection", "start_server",
                         "StreamReader", "StreamWriter"):
                assert gone not in src, (name, gone)


def test_a_handshake_that_never_comes_does_not_hold_shutdown():
    async def main():
        server = Messenger("osd.1")
        addr = await server.bind()
        r, w = await asyncio.open_connection(*addr)     # and says nothing
        await asyncio.sleep(0.05)
        assert len(server._accepting) == 1
        await asyncio.wait_for(server.shutdown(), 5)
        assert not server._accepting
        assert await r.read() == frames.BANNER          # then EOF
        w.close()
        await w.wait_closed()

    run(main())


def test_radoslint_sees_the_two_views_the_endpoint_keeps(tmp_path):
    """The lifetime rules hold over the new module: clean as it stands,
    and with its two justified suppressions taken out exactly the
    endpoint's window on its own spill and the body being filled are
    what `view-escape` reports: no frame segment is kept anywhere."""
    from ceph_tpu.tools.radoslint import core
    import ceph_tpu.msg.transport as mod
    assert core.run_lint([mod.__file__],
                         root=os.path.dirname(mod.__file__)) == []
    with open(mod.__file__) as f:
        bare = "\n".join(ln for ln in f.read().splitlines()
                         if "radoslint: disable" not in ln)
    (tmp_path / "transport.py").write_text(bare)
    found = core.run_lint([str(tmp_path / "transport.py")],
                          root=str(tmp_path))
    assert sorted((f.rule, f.message.split(" stored on ")[1].split(":")[0])
                  for f in found) == [("view-escape", "self._dest"),
                                      ("view-escape", "self._spill_mv")]


# -- the receive worker (msg/rxworker.py): a large body crosses the socket on
# -- a native thread, and everything above holds with it and without it -------

LINE = rxworker.LINE


def _with_native(monkeypatch, wanted: bool) -> None:
    """The worker's native library for this test: there (skipped where
    it is not built), or made unavailable."""
    if not wanted:
        monkeypatch.setattr(rxworker, "_checked", True)
        monkeypatch.setattr(rxworker, "_lib", None)
    elif not rxworker.available():
        pytest.skip("the native library is not built here")


@pytest.fixture(params=["worker", "no_native"])
def rx(request, monkeypatch):
    """With the receive worker, and with the native library made
    unavailable: the endpoint's own path then serves the same cases."""
    _with_native(monkeypatch, request.param == "worker")
    return request.param


def _fds() -> int:
    return len(os.listdir("/proc/self/fd"))


def _threads(settle: int | None = None) -> int:
    """Tasks of this process; with `settle`, once no more than that many
    are left or two seconds have passed: a thread that was joined is in
    `/proc` a moment longer (the join returns when it clears its tid)."""
    end = time.monotonic() + 2.0
    while (n := len(os.listdir("/proc/self/task"))) > (settle or n) \
            and time.monotonic() < end:
        time.sleep(0.01)
    return n


def _worker_is_gone() -> None:
    assert not rxworker._jobs and not rxworker._ports
    assert not rxworker.running()
    if rxworker.available():
        assert rxworker._lib.rxw_jobs() == 0


def _worker_delta(before: dict) -> dict:
    now = msgr_perf().dump()
    return {k: now[k] - before[k] for k in now if k.startswith("rx_")}


@pytest.mark.parametrize("head", ["head_in_spill", "no_head"])
@pytest.mark.parametrize("n", [LINE - 1, LINE, LINE + 1],
                         ids=["under", "at", "over"])
def test_a_body_round_the_line_is_the_senders_bytes(rx, n, head):
    """The body's length alone says who receives it; whoever does, it is
    the sender's bytes, and the small frame behind it is intact."""
    payload = os.urandom(n - 4)
    blob = Frame(Tag.MESSAGE, [payload]).encode()
    assert len(blob) == 12 + n
    small = Frame(Tag.ACK, [b"[7]"]).encode()

    async def main():
        srv, cli, server = await _socket_pair()
        before = dict(msgr_perf().dump())
        read = asyncio.create_task(Frame.read(srv))
        if head == "no_head":
            cli.write(blob[:12])
            while srv.body_filled() != 0:
                await asyncio.sleep(0.005)
            cli.write(blob[12:] + small)
        else:
            cli.write(blob + small)
        got = await read
        ack = await Frame.read(srv)
        d = _worker_delta(before)
        await _close(server, srv, cli)
        return got, ack, d

    got, ack, d = run(main())
    assert bytes(got.segments[0]) == payload
    assert (ack.tag, bytes(ack.segments[0])) == (Tag.ACK, b"[7]")
    worked = rx == "worker" and n >= LINE
    assert d["rx_worker_bodies"] == (1 if worked else 0)
    if head == "no_head":
        assert d["rx_worker_bytes"] == (n if worked else 0)
    else:
        assert (0 < n - d["rx_worker_bytes"] <= NARROW) if worked \
            else d["rx_worker_bytes"] == 0
    assert d["rx_direct_bytes"] + d["rx_spill_bytes"] == len(blob) + len(small)
    _worker_is_gone()


def test_the_worker_takes_not_one_byte_past_the_bodys_end(rx):
    """Large bodies and small frames written in one go: every small
    frame behind a body is read whole after it."""
    rng = random.Random(3)
    frames_ = []
    for i in range(12):
        frames_.append(Frame(Tag.MESSAGE,
                             [b"h%d" % i, rng.randbytes(LINE + rng.randrange(
                                 3 * LINE)), b"t"]))
        frames_.append(Frame(Tag.ACK, [b"[%d]" % i]))
    wire = b"".join(bytes(f.encode()) for f in frames_)

    async def main():
        srv, cli, server = await _socket_pair()
        cli.write(wire)
        got = [await Frame.read(srv) for _ in frames_]
        await _close(server, srv, cli)
        return got

    got = run(main())
    assert [(g.tag, [bytes(s) for s in g.segments]) for g in got] == \
        [(f.tag, [bytes(s) for s in f.segments]) for f in frames_]
    _worker_is_gone()


@pytest.mark.parametrize("seg", [0, 1, 2])
def test_a_bit_flipped_on_the_wire_above_the_line_faults_the_read(rx, seg):
    """Every segment's crc is checked before the frame is parsed, by
    whoever received the body."""
    segs = [os.urandom(LINE), os.urandom(2 * LINE), os.urandom(LINE // 2)]
    blob = bytearray(Frame(Tag.MESSAGE, segs).encode())
    at = 8 + 4 * 3 + sum(len(s) + 4 for s in segs[:seg]) + 1000
    blob[at] ^= 0x10

    async def main():
        srv, cli, server = await _socket_pair()
        cli.write(bytes(blob))
        with pytest.raises(FrameError, match="segment crc mismatch"):
            await Frame.read(srv)
        await _close(server, srv, cli)

    run(main())
    _worker_is_gone()


class _FlipOnce(_Relay):
    """A relay in front of `addr` that flips one bit of the bytes going
    to it, `at` bytes into the first connection, and is honest after."""

    def __init__(self, addr, at):
        super().__init__(addr)
        self.at, self.flipped = at, 0

    def _passing(self, data, seen, to_addr):
        if to_addr and not self.flipped and seen <= self.at < seen + len(data):
            data = bytearray(data)
            data[self.at - seen] ^= 0x01
            self.flipped += 1
        return data


def test_a_flipped_bit_faults_the_connection_and_the_session_replays(rx):
    """As today: the crc mismatch is a fault of the transport, the
    lossless session reconnects, and every message arrives once."""
    N = 5

    async def main():
        server = Messenger("osd.1")
        col = Collector()
        server.add_dispatcher(col)
        addr = await server.bind()
        relay = _FlipOnce(addr, 3 * (1 << 20))
        client = Messenger("osd.2")
        conn = await client.connect(await relay.start(),
                                    Policy.lossless_peer())
        datas = [os.urandom((1 << 20) + i) for i in range(N)]
        for i, d in enumerate(datas):
            conn.send_message(MOSDECSubOpWrite({"i": i}, d))
        await _wait_for(col, N)
        assert relay.flipped == 1
        assert [m.payload["i"] for m in col.messages] == list(range(N))
        assert all(m.data == d for m, d in zip(col.messages, datas))
        await client.shutdown()
        await server.shutdown()
        await relay.stop()

    run(main(), timeout=90)
    _worker_is_gone()


@pytest.mark.filterwarnings("ignore:unclosed:ResourceWarning")
@pytest.mark.parametrize("how", ["close", "cancelled_read", "abort",
                                 "peer_closes", "loop_dies"])
def test_a_body_given_up_half_received_leaves_nothing_behind(how):
    """No job, no fd and no thread are left, and the worker does not
    write into the buffer it was handed once the endpoint has it back
    (the body's unfilled half stays as the test left it)."""
    if not rxworker.available():
        pytest.skip("the native library is not built here")
    n = 4 << 20
    fds0 = _fds()

    async def main():
        srv, cli, server = await _socket_pair()
        before = dict(msgr_perf().dump())
        read = asyncio.create_task(srv.readexactly(n))
        cli.write(b"x" * (n // 2))
        while srv.body_filled() < n // 2:
            await asyncio.sleep(0.005)
        job = srv._job
        buf = job.buf
        assert rxworker._lib.rxw_jobs() == 1
        if how == "loop_dies":
            return buf      # nothing is closed, the read is cancelled
        if how == "close":
            srv.close()
        elif how == "cancelled_read":
            read.cancel()
        elif how == "abort":
            srv.transport.abort()
        else:
            cli.close()
        with pytest.raises((asyncio.IncompleteReadError, ConnectionError,
                            asyncio.CancelledError)):
            await read
        assert rxworker._lib.rxw_jobs() == 0 and not rxworker._jobs
        del job
        buf[n // 2:] = bytes(n // 2)
        cli.write(b"y" * 100000) if how != "peer_closes" else None
        await asyncio.sleep(0.05)
        assert buf[n // 2:] == bytes(n // 2) and buf[:n // 2] == \
            b"x" * (n // 2)
        d = _worker_delta(before)
        assert d["rx_worker_cancelled"] == (0 if how == "peer_closes" else 1)
        assert d["rx_worker_bytes"] == n // 2
        await _close(server, srv, cli)
        return buf

    buf = run(main())
    if how == "loop_dies":
        # the cancelled read took its job back; the endpoints were never
        # closed, so their loop's port stands until another loop asks
        assert rxworker._lib.rxw_jobs() == 0 and not rxworker._jobs

        async def again():
            srv, cli, server = await _socket_pair()
            cli.write(bytes(LINE))
            assert await srv.readexactly(LINE) == bytes(LINE)
            await _close(server, srv, cli)
        import gc
        gc.collect()        # the dead loop's transports close their sockets
        run(again())
    del buf
    _worker_is_gone()
    assert _fds() <= fds0


@pytest.mark.parametrize("seed", range(5))
def test_a_reconnect_storm_loses_nothing_and_leaves_nothing(rx, seed):
    """Lossless sessions under aborts in mid-body from either end, with
    duplicated and delayed messages injected (qa/faultinject): every
    message arrives once, in order, byte for byte, and when the
    messengers are shut down no job, fd or thread of the worker is
    left."""
    from ceph_tpu.qa import faultinject
    N = 24
    fds0, threads0 = _fds(), _threads()

    async def main():
        rng = random.Random(seed)
        server = Messenger("osd.1")
        col = Collector()
        server.add_dispatcher(col)
        addr = await server.bind()
        relay = _Relay(addr)
        client = Messenger("osd.2")
        back = Collector()
        client.add_dispatcher(back)
        conn = await client.connect(await relay.start(),
                                    Policy.lossless_peer())
        datas = [rng.randbytes(rng.choice([LINE - 5, LINE, 3 * LINE, 1 << 20])
                               + i) for i in range(N)]
        aborts = 0

        def arrived() -> int:       # an injected dup is dispatched twice
            return len({m.payload["i"] for m in col.messages})

        async def storm():
            nonlocal aborts
            while arrived() < N:
                await asyncio.sleep(0)
                for c in list(server._sessions.values()):
                    ep = c._reader
                    if aborts >= 6 or ep is None or \
                            ep.body_filled() < rng.randrange(LINE // 4):
                        continue
                    aborts += 1
                    for v in rng.choice([[conn], [c], [conn, c]]):
                        if v._writer is not None:
                            v._writer.transport.abort()
                    await asyncio.sleep(rng.random() * 0.02)
                    break

        inj = faultinject.get_injector()
        inj.reset(seed)
        was = inj.msg_dup, inj.msg_delay, inj.msg_delay_ms
        inj.msg_dup, inj.msg_delay, inj.msg_delay_ms = 0.1, 0.1, 5.0
        faultinject.set_enabled(True)
        try:
            stormer = asyncio.create_task(storm())
            for i, d in enumerate(datas):
                conn.send_message(MOSDECSubOpWrite({"i": i}, d))
                if i % 5 == 0:
                    await asyncio.sleep(0.01)
            await asyncio.wait_for(stormer, 60)
        finally:
            faultinject.set_enabled(False)
            inj.msg_dup, inj.msg_delay, inj.msg_delay_ms = was
            inj.reset(0)
        got = {}
        for m in col.messages:
            got.setdefault(m.payload["i"], m)
        assert sorted(got) == list(range(N))
        assert all(got[i].data == d for i, d in enumerate(datas))
        assert aborts >= 1
        await client.shutdown()
        await server.shutdown()
        await relay.stop()

    run(main(), timeout=120)
    _worker_is_gone()
    assert _fds() <= fds0 and _threads(threads0) <= threads0


@pytest.mark.parametrize("mode", _MODES)
def test_onwire_sessions_carry_a_4mib_message(rx, mode):
    """A secure or compressed session's envelope is one read of its
    length: above the line the worker receives it, and verifies no crc
    (the envelope has none; GCM and the inner frame's crcs do)."""
    async def main():
        key = b"k" * 16
        server = Messenger("osd.1", auth_key=key, **mode)
        col = Collector()
        server.add_dispatcher(col)
        addr = await server.bind()
        client = Messenger("osd.2", auth_key=key, **mode)
        conn = await client.connect(addr, Policy.lossless_peer())
        data = os.urandom(4 << 20)
        before = dict(msgr_perf().dump())
        conn.send_message(MOSDECSubOpWrite({"i": 0}, data))
        conn.send_message(MPing({"i": 1}))
        await _wait_for(col, 2)
        d = _worker_delta(before)
        assert bytes(col.messages[0].data) == data
        assert col.messages[1].payload["i"] == 1
        await client.shutdown()
        await server.shutdown()
        return d

    d = run(main())
    if rx == "worker":
        assert d["rx_worker_bodies"] == 1
        assert d["rx_worker_bytes"] >= (4 << 20) - NARROW
    else:
        assert d["rx_worker_bodies"] == d["rx_worker_bytes"] == 0
    _worker_is_gone()


def test_no_fd_and_no_thread_outlives_the_last_messenger():
    if not rxworker.available():
        pytest.skip("the native library is not built here")
    fds0, threads0 = _fds(), _threads()

    async def main():
        a, b = Messenger("osd.1"), Messenger("osd.2")
        col = Collector()
        a.add_dispatcher(col)
        conn = await b.connect(await a.bind(), Policy.lossless_peer())
        conn.send_message(MOSDECSubOpWrite({"i": 0}, bytes(1 << 20)))
        await _wait_for(col, 1)
        assert rxworker.running() and \
            _threads() == threads0 + rxworker.WORKERS
        await b.shutdown()
        # the acceptor's endpoint used the worker, and it still stands
        assert rxworker.running()
        await a.shutdown()
        assert not rxworker.running()

    run(main())
    _worker_is_gone()
    assert _fds() <= fds0 and _threads(threads0) == threads0


@pytest.mark.filterwarnings("ignore:This process .* is multi-threaded")
@pytest.mark.parametrize("direction", ["receive", "send"])
def test_a_forked_child_starts_a_worker_of_its_own(direction):
    if not rxworker.available():
        pytest.skip("the native library is not built here")

    async def body_through_worker(keep: list):
        srv, cli, server = await _socket_pair()
        before = dict(msgr_perf().dump())
        data = os.urandom(2 * LINE)
        if direction == "send":
            frame = Frame(Tag.MESSAGE, [data])
            sent = asyncio.create_task(_send(cli, [], frame))
            assert await srv.readexactly(12 + len(data) + 4) == \
                frame.encode()
            assert await sent == "worker"
            assert _tx_delta(before)["tx_worker_bodies"] == 1
        else:
            cli.write(data)
            assert await srv.readexactly(len(data)) == data
        assert _worker_delta(before)["rx_worker_bodies"] == 1
        keep += [srv, cli, server]

    loop = asyncio.new_event_loop()
    keep: list = []
    try:
        loop.run_until_complete(body_through_worker(keep))
        assert rxworker.running()       # the parent's, its endpoints open
        pid = os.fork()
        if pid == 0:
            code = 1
            try:
                assert not rxworker.running() and not rxworker._ports
                asyncio.run(asyncio.wait_for(body_through_worker([]), 30))
                assert rxworker.running()
                code = 0
            finally:
                os._exit(code)
        assert os.waitpid(pid, 0)[1] == 0
        # and the parent's worker still serves the parent

        async def again():
            srv, cli = keep[0], keep[1]
            cli.write(bytes(LINE))
            assert await srv.readexactly(LINE) == bytes(LINE)
            await _close(keep[2], srv, cli)
        loop.run_until_complete(again())
    finally:
        loop.close()
    _worker_is_gone()


# -- the send worker: a large frame leaves the socket on the native thread, and
# -- the wire is what it was -------------------------------------------------

def _tx_delta(before: dict) -> dict:
    now = msgr_perf().dump()
    return {k: now[k] - before[k] for k in now if k.startswith("tx_")}


async def _send(ep: Endpoint, head: list, frame: Frame) -> str:
    """What the write loop does with a plain-crc frame and the small
    frames gathered in front of it; returns who sent it."""
    parts = [f.encode() for f in head]
    nbytes = frame.payload_len()
    if ep.worker_sends(nbytes):
        job = ep.send_frame(parts, frame)
        if job is not None:
            await ep.frame_sent(job)
            return "worker"
    parts.extend(frame.encode_parts() if nbytes >= SPILL_SIZE
                 else [frame.encode()])
    ep.writelines(parts)
    await ep.drain()
    return "transport"


_TX_SHAPES = {
    "one_segment": lambda r, n: [r.randbytes(n)],
    "two_segments": lambda r, n: [b'{"type":112}', r.randbytes(n - 12)],
    "four_segments": lambda r, n: [b"h" * 30, b"", r.randbytes(n - 48),
                                   b"\x7c\xec" * 9],
    "several_live_parts": lambda r, n: [
        b"h" * 30, [r.randbytes(n // 3), b"",
                    memoryview(r.randbytes(n // 3)).toreadonly(),
                    bytearray(r.randbytes(n - 30 - 2 * (n // 3)))]],
}


@pytest.mark.parametrize("shape", list(_TX_SHAPES))
@pytest.mark.parametrize("n", [LINE - 1, LINE, 4 << 20],
                         ids=["under", "at", "4m"])
def test_a_frame_round_the_line_is_encode_byte_for_byte(rx, codec, n, shape):
    """The payload's length alone says who sends a frame; whoever does,
    the peer reads `Frame.encode()`'s bytes, crcs included, with the
    small frames framed in front of it before it and one after it."""
    rng = random.Random(f"{n}{shape}")
    frame = Frame(Tag.MESSAGE, _TX_SHAPES[shape](rng, n))
    assert frame.payload_len() == n
    ack, ping = Frame(Tag.ACK, [b"[7]"]), Frame(Tag.KEEPALIVE, [])
    want = b"".join(bytes(f.encode()) for f in (ack, ping, frame, ack))

    async def main():
        srv, cli, server = await _socket_pair()
        before = dict(msgr_perf().dump())
        read = asyncio.create_task(srv.readexactly(len(want)))
        who = await _send(cli, [ack, ping], frame)
        assert await _send(cli, [], ack) == "transport"
        got = await read
        d = _tx_delta(before)
        await _close(server, srv, cli)
        return who, bytes(got), d

    who, got, d = run(main())
    assert got == want
    worked = rx == "worker" and n >= LINE
    assert who == ("worker" if worked else "transport")
    assert d["tx_worker_bodies"] == (1 if worked else 0)
    assert d["tx_worker_bytes"] == (n if worked else 0)
    assert (d["tx_worker_cpu_ns"] > 0) == worked
    assert d["tx_worker_declined"] == d["tx_worker_cancelled"] == 0
    _worker_is_gone()


def test_a_send_into_a_small_buffer_completes_over_several_rounds(rx):
    """4 MiB through a socket whose send buffer holds 128 KiB: the
    worker waits for `EPOLLOUT` between rounds while the loop reads the
    other end, and the loop is woken once."""
    frame = Frame(Tag.MESSAGE, [b"h", os.urandom(4 << 20)])

    async def main():
        srv, cli, server = await _socket_pair()
        cli.get_extra_info("socket").setsockopt(
            socket.SOL_SOCKET, socket.SO_SNDBUF, 131072)
        woken = 0
        if rx == "worker":
            port = rxworker.acquire(asyncio.get_running_loop())
            real = port._reap

            def reap():
                nonlocal woken
                woken += 1
                real()
            asyncio.get_running_loop().remove_reader(port.efd)
            asyncio.get_running_loop().add_reader(port.efd, reap)
        sent = asyncio.create_task(_send(cli, [], frame))
        await asyncio.sleep(0.05)       # the socket is full by now
        assert not sent.done()
        # the reader takes it from the transport, a chunk a turn
        got = bytearray()
        while len(got) < frame.payload_len() + 24:
            got += await srv.readexactly(
                min(SPILL_SIZE, frame.payload_len() + 24 - len(got)))
        who = await sent
        if rx == "worker":
            rxworker.release(port)
        await _close(server, srv, cli)
        return who, bytes(got), woken

    who, got, woken = run(main())
    assert got == frame.encode()
    assert who == ("worker" if rx == "worker" else "transport")
    assert woken == (1 if rx == "worker" else 0)
    _worker_is_gone()


def test_frames_leave_in_queue_order_round_large_ones(rx, codec):
    """Small and large messages queued in turn on one session arrive in
    the queue's order, each byte for byte; the large ones were the
    worker's where there is one and the small ones never."""
    N = 30

    async def main():
        server = Messenger("osd.1")
        col = Collector()
        server.add_dispatcher(col)
        addr = await server.bind()
        client = Messenger("osd.2")
        conn = await client.connect(addr, Policy.lossless_peer())
        rng = random.Random(2)
        datas = [rng.randbytes(rng.choice([LINE, 3 * LINE]) if i % 3 == 1
                               else rng.randrange(0, 3000))
                 for i in range(N)]
        before = dict(msgr_perf().dump())
        for i, d in enumerate(datas):
            conn.send_message(MOSDECSubOpWrite({"i": i}, d) if i % 3
                              else MPing({"i": i, "d": d.hex()}))
            if i % 7 == 0:
                await asyncio.sleep(0)
        await _wait_for(col, N)
        d = _tx_delta(before)
        assert [m.payload["i"] for m in col.messages] == list(range(N))
        for m, want in zip(col.messages, datas):
            got = bytes.fromhex(m.payload["d"]) if "d" in m.payload \
                else bytes(m.data)
            assert got == want
        await client.shutdown()
        await server.shutdown()
        return d

    d = run(main())
    large = N // 3
    assert d["tx_worker_bodies"] == (large if rx == "worker" else 0)
    assert d["tx_worker_declined"] == d["tx_worker_cancelled"] == 0
    _worker_is_gone()


@pytest.mark.parametrize("case", [
    "over_the_line", "under_the_line", "no_native",
    pytest.param("compressed", id="onwire_compressed"),
    pytest.param("secure", id="onwire_secure", marks=pytest.mark.skipif(
        not _HAVE_CRYPTO, reason="needs 'cryptography'"))])
def test_what_reaches_the_send_worker_and_what_never_does(case, monkeypatch):
    """A plain-crc frame from the line up, and nothing else: not a frame
    under it, not a secure or compressed session's, and none where the
    library is missing; those leave as at the parent commit."""
    _with_native(monkeypatch, case != "no_native")
    mode = {"compressed": {"compress": True},
            "secure": {"secure": True}}.get(case, {})
    key = b"k" * 16 if mode else None
    n = LINE - 200 if case == "under_the_line" else 2 * LINE
    calls = []
    real = rxworker.submit_tx
    monkeypatch.setattr(rxworker, "submit_tx",
                        lambda *a: calls.append(a) or real(*a))

    async def main():
        server = Messenger("osd.1", auth_key=key, **mode)
        col = Collector()
        server.add_dispatcher(col)
        addr = await server.bind()
        client = Messenger("osd.2", auth_key=key, **mode)
        conn = await client.connect(addr, Policy.lossless_peer())
        data = os.urandom(n)
        before = dict(msgr_perf().dump())
        conn.send_message(MOSDECSubOpWrite({"i": 0}, data))
        await _wait_for(col, 1)
        d = _tx_delta(before)
        assert bytes(col.messages[0].data) == data
        await client.shutdown()
        await server.shutdown()
        return d

    d = run(main())
    worked = case == "over_the_line"
    assert len(calls) == d["tx_worker_bodies"] == (1 if worked else 0)
    assert d["tx_worker_declined"] == 0
    if worked:
        assert n <= d["tx_worker_bytes"] == d["tx_direct_bytes"] < n + 200
    else:
        assert d["tx_worker_bytes"] == d["tx_worker_cpu_ns"] == 0
        assert (d["tx_copied_bytes"] if mode else d["tx_direct_bytes"]) >= n
    _worker_is_gone()


def _submit_fails(*a):
    raise OSError(24, "Too many open files")


@pytest.mark.parametrize("why", ["queue_not_empty", "submit_fails"])
def test_a_frame_the_worker_cannot_have_goes_the_transports_way(
        why, monkeypatch):
    """Bytes still in the transport's own queue, or a submit that fails:
    the frame is declined (`tx_worker_declined`), the transport sends it
    behind what it holds, and the peer reads both, in order."""
    if not rxworker.available():
        pytest.skip("the native library is not built here")
    frame = Frame(Tag.MESSAGE, [b"h", os.urandom(2 * LINE)])
    first = os.urandom(3 << 20) if why == "queue_not_empty" else b"first"

    async def main():
        srv, cli, server = await _socket_pair()
        if why == "submit_fails":
            monkeypatch.setattr(rxworker, "submit_tx", _submit_fails)
        before = dict(msgr_perf().dump())
        want = first + bytes(frame.encode())
        read = asyncio.create_task(srv.readexactly(len(want)))
        if why == "queue_not_empty":
            cli.get_extra_info("socket").setsockopt(
                socket.SOL_SOCKET, socket.SO_SNDBUF, 65536)
        cli.write(first)
        if why == "queue_not_empty":
            assert cli.transport.get_write_buffer_size() > 0
        assert cli.worker_sends(frame.payload_len())
        assert cli.send_frame([], frame) is None
        who = await _send(cli, [], frame)
        got = await read
        d = _tx_delta(before)
        await _close(server, srv, cli)
        return who, bytes(got) == want, d

    who, same, d = run(main())
    assert who == "transport" and same
    assert d["tx_worker_declined"] == 2 and d["tx_worker_bodies"] == 0
    _worker_is_gone()


def test_a_declined_frame_of_a_session_arrives_all_the_same(monkeypatch):
    """The write loop's own fall-back: every submit fails, every message
    arrives, and each large one is counted declined and by reference."""
    if not rxworker.available():
        pytest.skip("the native library is not built here")
    monkeypatch.setattr(rxworker, "submit_tx", _submit_fails)

    async def main():
        server = Messenger("osd.1")
        col = Collector()
        server.add_dispatcher(col)
        addr = await server.bind()
        client = Messenger("osd.2")
        conn = await client.connect(addr, Policy.lossless_peer())
        datas = [os.urandom(LINE + i) for i in range(4)]
        before = dict(msgr_perf().dump())
        for i, d in enumerate(datas):
            conn.send_message(MOSDECSubOpWrite({"i": i}, d))
            await asyncio.sleep(0.01)
        await _wait_for(col, 4)
        d = _tx_delta(before)
        assert [bytes(m.data) for m in col.messages] == datas
        await client.shutdown()
        await server.shutdown()
        return d

    d = run(main())
    assert d["tx_worker_declined"] == 4 and d["tx_worker_bodies"] == 0
    assert d["tx_direct_bytes"] >= 4 * LINE
    _worker_is_gone()


@pytest.mark.filterwarnings("ignore:unclosed:ResourceWarning")
@pytest.mark.parametrize("how", ["close", "cancelled_write", "abort",
                                 "peer_closes"])
def test_a_frame_given_up_half_sent_leaves_nothing_behind(how):
    """The job is taken back before the fault is raised: no job, no fd
    and no thread are left, the thread lets go of every part (not one
    byte leaves after the cancel), and a write loop that is cancelled
    with a frame cut short on the wire takes the transport with it."""
    if not rxworker.available():
        pytest.skip("the native library is not built here")
    fds0 = _fds()
    frame = Frame(Tag.MESSAGE, [b"h", os.urandom(4 << 20)])
    wire = bytes(frame.encode())

    async def main():
        srv, cli, server = await _socket_pair()
        cli.get_extra_info("socket").setsockopt(
            socket.SOL_SOCKET, socket.SO_SNDBUF, 65536)
        srv.transport.pause_reading()       # the peer takes nothing
        before = dict(msgr_perf().dump())
        sent = asyncio.create_task(_send(cli, [], frame))
        while cli._tx_job is None or rxworker.progress(cli._tx_job) <= 0:
            await asyncio.sleep(0.005)
        await asyncio.sleep(0.05)
        assert rxworker._lib.rxw_jobs() == 1 and not sent.done()
        if how == "close":
            cli.close()
        elif how == "cancelled_write":
            sent.cancel()
        elif how == "abort":
            cli.transport.abort()
        else:
            srv.transport.abort()
        with pytest.raises((ConnectionError, OSError,
                            asyncio.CancelledError)):
            await sent
        assert rxworker._lib.rxw_jobs() == 0 and not rxworker._jobs
        assert cli._tx_job is None
        # cut short on the wire: nobody may send behind it
        assert cli.transport.is_closing()
        d = _tx_delta(before)
        if how == "peer_closes":
            # the thread saw the reset itself, or the loop took the job
            # back when it saw it: either way it is whole or counted
            assert d["tx_worker_cancelled"] + d["tx_worker_bodies"] <= 1
        else:
            assert d["tx_worker_cancelled"] == 1
            assert 0 < d["tx_worker_bytes"] < 4 << 20
            srv.transport.resume_reading()
            got = bytearray()
            try:
                while chunk := await srv.readexactly(1):
                    got += chunk
            except (asyncio.IncompleteReadError, ConnectionError) as e:
                got += getattr(e, "partial", b"")
            assert len(got) < len(wire) and wire.startswith(bytes(got))
        assert d["tx_worker_bodies"] == 0 or how == "peer_closes"
        await _close(server, srv, cli)

    run(main())
    _worker_is_gone()
    assert _fds() <= fds0


@pytest.mark.filterwarnings("ignore:unclosed:ResourceWarning")
def test_a_loop_that_dies_with_endpoints_open_leaks_no_send_dup():
    """`connection_lost` never runs for an endpoint whose loop is gone:
    the dup the worker sent on goes with the endpoint, as the socket
    goes with its object."""
    if not rxworker.available():
        pytest.skip("the native library is not built here")
    import gc
    gc.collect()
    fds0 = _fds()

    async def main():
        srv, cli, server = await _socket_pair()
        frame = Frame(Tag.MESSAGE, [os.urandom(LINE)])
        read = asyncio.create_task(Frame.read(srv))
        assert await _send(cli, [], frame) == "worker"
        assert bytes((await read).segments[0]) == bytes(frame.segments[0])
        assert cli._tx_fd >= 0
        server.close()      # the two endpoints stay open

    run(main())
    # the dead loop's port, which holds the loop and so its endpoints,
    # goes when the next loop asks for one
    run(_a_body_crosses())
    gc.collect()
    _worker_is_gone()
    assert _fds() <= fds0


async def _a_body_crosses():
    srv, cli, server = await _socket_pair()
    cli.write(bytes(LINE))
    assert await srv.readexactly(LINE) == bytes(LINE)
    await _close(server, srv, cli)

