"""The messenger's own socket endpoint (msg/transport.py): frames fed in
every chunking decode to what `Frame.decode` gives, a body lands in its
own buffer, EOF and abort raise in the pending read, a full spill pauses
reading, and the sessions built on it keep their guarantees."""
from __future__ import annotations

import asyncio
import os
import random
import zlib

import pytest

from ceph_tpu.msg import frames
from ceph_tpu.msg.frames import (Frame, FrameError, Onwire, Tag,
                                 encode_trace_ctx)
from ceph_tpu.msg.messages import MOSDECSubOpWrite, MPing
from ceph_tpu.msg.messenger import Messenger, Policy, msgr_perf
from ceph_tpu.msg import transport
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
        while srv._dest is None or srv._dest_pos < n // 2:
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
        client = Messenger("osd.2")
        conn = await client.connect(addr, Policy.lossless_peer())
        rng = random.Random(7)
        datas = [rng.randbytes((1 << 20) + i) for i in range(N)]
        aborts = 0

        async def yank():
            nonlocal aborts
            while aborts < 3:
                await asyncio.sleep(0)
                for c in list(server._sessions.values()):
                    ep = c._reader
                    if ep is None or ep._dest is None or \
                            ep._dest_pos < 100000:
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
