"""Native frame codec (native/ec_native.cc frame_pack/frame_verify_body
via ceph_tpu/native/frame_native.py): build-or-skip in the test
environment, fuzzed bit-identity against the pure-Python frames.py path
(random segment counts/sizes, scatter segments, truncated preambles,
corrupt crcs), and the tier-1 guarantee that the Python fallback passes
the whole frame suite with the native codec force-disabled.
"""
from __future__ import annotations

import random

import pytest

from ceph_tpu.msg import frames
from ceph_tpu.msg.frames import MAGIC, Frame, FrameError, Tag
from ceph_tpu.msg.transport import SPILL_SIZE
from ceph_tpu.native import NativeUnavailable


def _native_or_skip() -> None:
    """Build libec_native.so if missing; skip (not fail) when the test
    environment has no compiler — the CI build satellite."""
    try:
        from ceph_tpu import native
        native.load()
    except NativeUnavailable as e:
        pytest.skip(f"native library unavailable: {e}")
    from ceph_tpu.native import frame_native
    if not frame_native.available():
        pytest.skip("libec_native.so predates the frame codec")


@pytest.fixture
def both_codecs():
    """Yields after ensuring native is available; restores the original
    codec selection afterwards."""
    _native_or_skip()
    was = frames.native_active()
    yield
    frames.set_native(was)


def _rand_frame(rng: random.Random) -> Frame:
    nseg = rng.randint(0, 4)
    segs: list = []
    for _ in range(nseg):
        if rng.random() < 0.3:
            # scatter segment: 1..4 parts, mixed bytes-like types
            parts: list = []
            for _ in range(rng.randint(1, 4)):
                raw = rng.randbytes(rng.randint(0, 700))
                kind = rng.random()
                if kind < 0.33:
                    parts.append(raw)
                elif kind < 0.66:
                    parts.append(bytearray(raw))
                else:
                    parts.append(memoryview(raw))
            segs.append(parts)
        else:
            segs.append(rng.randbytes(rng.randint(0, 3000)))
    return Frame(rng.choice(list(Tag)), segs)


def _flat_segments(segs: list) -> list[bytes]:
    return [b"".join(bytes(x) for x in s) if isinstance(s, (list, tuple))
            else bytes(s) for s in segs]


def test_native_python_fuzz_parity(both_codecs):
    """Random frames encode bit-identically under both codecs and
    cross-decode: native-encoded bytes parse under Python and vice
    versa, with the same segments out."""
    rng = random.Random(0xEC02)
    for trial in range(300):
        f = _rand_frame(rng)
        assert frames.set_native(True)
        nat = bytes(f.encode())
        nat_parts = b"".join(bytes(p) for p in f.encode_parts())
        frames.set_native(False)
        py = f.encode()
        py_parts = b"".join(bytes(p) for p in f.encode_parts())
        assert nat == py == nat_parts == py_parts, trial
        flat = _flat_segments(f.segments)
        for native_decode in (True, False):
            frames.set_native(native_decode)
            got = Frame.decode(nat)
            assert got.tag == f.tag
            assert [bytes(s) for s in got.segments] == flat, trial


def test_truncations_and_corruptions_agree(both_codecs):
    """Every truncation point and single-bit payload corruption raises
    FrameError under BOTH codecs (fuzzing the error paths, not just the
    happy one)."""
    rng = random.Random(7)
    f = Frame(Tag.MESSAGE, [b"hdr", rng.randbytes(513), b""])
    frames.set_native(True)
    blob = f.encode()
    cuts = list(range(0, 12)) + [len(blob) - 9, len(blob) - 4,
                                 len(blob) - 1]
    for use_native in (True, False):
        frames.set_native(use_native)
        for cut in cuts:
            with pytest.raises(FrameError):
                Frame.decode(blob[:cut])
        # flip one bit in each region: preamble len, segment byte, crc
        for pos in (3, 6, 30, len(blob) - 2):
            bad = bytearray(blob)
            bad[pos] ^= 0x40
            with pytest.raises(FrameError):
                Frame.decode(bytes(bad))
        # bad magic
        with pytest.raises(FrameError):
            Frame.decode(b"\x00\x00" + blob[2:])


def test_python_fallback_passes_full_frame_suite():
    """Tier-1 contract: with the native codec force-disabled, the pure
    Python path alone passes the whole frame behavior suite (what a
    no-compiler deployment runs on)."""
    was = frames.native_active()
    frames.set_native(False)
    try:
        assert not frames.native_active()
        rng = random.Random(99)
        for _ in range(100):
            f = _rand_frame(rng)
            blob = f.encode()
            got = Frame.decode(blob)
            assert got.tag == f.tag
            assert [bytes(s) for s in got.segments] == \
                _flat_segments(f.segments)
        # preamble crc protects the lengths
        f = Frame(Tag.MESSAGE, [b"abc"])
        blob = bytearray(f.encode())
        blob[4] ^= 1                      # seg_len byte under pre-crc
        with pytest.raises(FrameError):
            Frame.decode(bytes(blob))
        # oversized segment bound still enforced
        import struct
        pre = struct.pack("<HBB", MAGIC, int(Tag.MESSAGE), 1)
        pre += struct.pack("<I", Frame.MAX_SEGMENT_SIZE + 1)
        pre += struct.pack("<I", frames.crc32c(pre))
        with pytest.raises(FrameError):
            Frame.decode(pre)
    finally:
        frames.set_native(was)


def test_set_native_disabled_under_env(both_codecs):
    """CEPH_TPU_FRAME_NATIVE=0 keeps the Python path: simulated via
    set_native — the import-time gate uses the same switch."""
    frames.set_native(False)
    f = Frame(Tag.MESSAGE, [b"x" * 100])
    parts = f.encode_parts()
    assert parts[1] is f.segments[0]      # scatter contract, no pack
    assert type(f.encode()) is bytes      # python: a join
    frames.set_native(True)
    assert f.encode_parts()[1] is f.segments[0]   # by reference too
    assert type(f.encode()) is bytearray  # native: one packed blob


# -- frames sent by reference ----------------------------------------------

LINE = SPILL_SIZE       # the write loop's line between packed and by reference


def _payload(n: int, kind: str):
    raw = random.Random(n).randbytes(n)
    return {"bytes": raw, "bytearray": bytearray(raw),
            "view": memoryview(raw), "rx_view": memoryview(
                bytearray(raw)).toreadonly()}[kind]


_SHAPES = {
    "no_segments": lambda n, k: [],
    "one_segment": lambda n, k: [_payload(n, k)],
    "message": lambda n, k: [b'{"type":112,"seq":9}', b'{"i":0}',
                             _payload(n, k)],
    "message_traced": lambda n, k: [b'{"type":112,"seq":9}', b'{"i":0}',
                                    _payload(n, k), b"\x7c\xec" + b"t" * 17],
    "empty_data": lambda n, k: [b"hdr", b"", b"", _payload(n, k)],
    "scatter": lambda n, k: [b"hdr", b"{}", [_payload(n // 2, k),
                                             _payload(n - n // 2, k)]],
    "scatter_with_empty_parts": lambda n, k: [
        b"hdr", [b"", _payload(n, k), b"", bytearray()], []],
}


@pytest.mark.parametrize("use_native", [True, False],
                         ids=["native", "python"])
@pytest.mark.parametrize("n", [0, 1, LINE - 1, LINE, LINE + 1, 512 << 10],
                         ids=lambda n: f"{n}B")
@pytest.mark.parametrize("shape", list(_SHAPES))
def test_parts_join_to_the_packed_blob(both_codecs, shape, n, use_native):
    """`b"".join(encode_parts())` is `encode()` byte for byte, under,
    at and over the line the write loop switches at; no part is empty
    (the transport would never finish a queue that ends in one); and
    every payload part is the caller's own object, not a copy."""
    for kind in ("bytes", "bytearray", "view", "rx_view"):
        frames.set_native(use_native)
        f = Frame(Tag.MESSAGE, _SHAPES[shape](n, kind))
        parts = f.encode_parts()
        blob = bytes(f.encode())
        assert b"".join(bytes(p) for p in parts) == blob
        assert all(len(p) for p in parts)
        given = [p for seg in f.segments
                 for p in (seg if isinstance(seg, list) else [seg])
                 if len(p)]
        sent = [p for p in parts if any(p is g for g in given)]
        assert len(sent) == len(given)
        assert all(a is b for a, b in zip(sent, given))
        # what is left is the preamble and a crc a segment
        assert len(parts) - len(sent) == 1 + len(f.segments)
        assert f.payload_len() == sum(len(g) for g in given)
        # the other codec's packed blob is the same wire
        frames.set_native(not use_native)
        assert bytes(f.encode()) == blob


# The receiver as the parent commit (PR 26, 18df274) had it, pure
# Python, frozen here: a change's frames must read on a parent's daemon
# and the other way round. Its crc is the table kernel, which this PR
# did not touch, so the new kernel does not vouch for itself.

def _parent_decode(blob: bytes) -> tuple[int, list[bytes]]:
    import struct
    from ceph_tpu.native import ec_native

    def crc(data, seed=0):
        return ec_native.crc32c_sw(bytes(data), seed)

    magic, tag, nseg = struct.unpack_from("<HBB", blob, 0)
    assert magic == 0xEC02 and nseg <= 4
    off = 4
    seg_lens = [struct.unpack_from("<I", blob, off + 4 * i)[0]
                for i in range(nseg)]
    (pre_crc,) = struct.unpack_from("<I", blob, off + 4 * nseg)
    assert crc(blob[:off + 4 * nseg]) == pre_crc, "preamble crc"
    off += 4 * nseg + 4
    segments = []
    for ln in seg_lens:
        seg = blob[off:off + ln]
        assert len(seg) == ln, "truncated"
        (seg_crc,) = struct.unpack_from("<I", blob, off + ln)
        assert crc(seg) == seg_crc, "segment crc"
        segments.append(bytes(seg))
        off += ln + 4
    assert off == len(blob)
    return tag, segments


def _parent_encode(tag: int, segments: list[bytes]) -> bytes:
    import struct
    from ceph_tpu.native import ec_native
    pre = struct.pack("<HBB", 0xEC02, tag, len(segments))
    for seg in segments:
        pre += struct.pack("<I", len(seg))
    pre += struct.pack("<I", ec_native.crc32c_sw(pre, 0))
    return pre + b"".join(
        seg + struct.pack("<I", ec_native.crc32c_sw(seg, 0))
        for seg in segments)


@pytest.mark.parametrize("use_native", [True, False],
                         ids=["native", "python"])
@pytest.mark.parametrize("n", [0, 700, LINE - 1, LINE, LINE + 1, 4 << 20],
                         ids=lambda n: f"{n}B")
def test_a_parents_receiver_reads_the_changes_frames(both_codecs, n,
                                                     use_native):
    frames.set_native(use_native)
    segs = [b'{"type":112,"seq":3}', b'{"i":1}',
            [_payload(n // 3, "bytes"), _payload(n - n // 3, "rx_view")]]
    f = Frame(Tag.MESSAGE, segs)
    flat = _flat_segments(segs)
    for wire in (b"".join(bytes(p) for p in f.encode_parts()),
                 bytes(f.encode())):
        assert _parent_decode(wire) == (int(Tag.MESSAGE), flat)
        assert wire == _parent_encode(int(Tag.MESSAGE), flat)
    # and the other way round: the parent's bytes through today's reader
    got = Frame.decode(_parent_encode(int(Tag.MESSAGE), flat))
    assert [bytes(s) for s in got.segments] == flat


# -- the receive worker's native side (native/ec_native.cc rxw_*) -------------

import ctypes  # noqa: E402
import errno  # noqa: E402
import os  # noqa: E402
import select  # noqa: E402
import socket  # noqa: E402


@pytest.fixture(params=[1, 2], ids=["one_thread", "two_threads"])
def rxw(request):
    """The library with the worker's threads running, and stopped after."""
    _native_or_skip()
    from ceph_tpu.msg import rxworker
    if not rxworker.available():
        pytest.skip("the library has no receive worker (not Linux)")
    assert not rxworker._ports, "an earlier test left the worker in use"
    lib = rxworker._lib
    n = request.param
    assert lib.rxw_start(n) == 0 and lib.rxw_start(n) == 0  # idempotent
    assert lib.rxw_running() == n
    yield lib
    assert lib.rxw_stop() == 0, "a job was left with the worker"
    assert lib.rxw_running() == 0


class _Wire:
    """A socket pair, a body buffer and an eventfd: what a job needs."""

    def __init__(self, n: int):
        self.a, self.b = socket.socketpair()
        self.a.setblocking(False)
        self.buf = bytearray(b"\xEE" * n)
        self.keep = ctypes.c_char.from_buffer(self.buf)
        self.efd = os.eventfd(0, os.EFD_NONBLOCK)

    def submit(self, lib, token, have, seg_lens):
        lens = (ctypes.c_uint64 * len(seg_lens))(*seg_lens) \
            if seg_lens else None
        return lib.rxw_submit(token, self.b.fileno(),
                              ctypes.addressof(self.keep), have,
                              len(self.buf), lens, len(seg_lens), self.efd)

    def reap(self, lib, timeout=10.0):
        """[(token, got, recvs, cpu_ns, bad, status)] once woken."""
        assert select.select([self.efd], [], [], timeout)[0], "no wake-up"
        os.eventfd_read(self.efd)
        out = (ctypes.c_int64 * 60)()
        done, n = [], 10
        while n == 10:      # the wake-up is for all that is in the ring
            n = lib.rxw_reap(out, 10)
            done += [tuple(out[i:i + 6]) for i in range(0, 6 * n, 6)]
        return done

    def close(self):
        del self.keep
        self.a.close()
        self.b.close()
        os.close(self.efd)


def _feed(sock, data: bytes, chunks) -> None:
    off = 0
    for c in chunks:
        if off >= len(data):
            break
        end = min(len(data), off + c)
        while off < end:
            try:
                off += sock.send(data[off:end])
            except BlockingIOError:
                select.select([], [sock], [], 1.0)
    while off < len(data):
        try:
            off += sock.send(data[off:])
        except BlockingIOError:
            select.select([], [sock], [], 1.0)


_SEGS = [[700_000], [1, 300_000, 0, 5], [0, 0, 262_144], [65_536] * 4]


@pytest.mark.parametrize("bad_seg", [-1, 0, "last"])
@pytest.mark.parametrize("have", [0, 1, 4093, "into_the_second"])
@pytest.mark.parametrize("seg_lens", _SEGS, ids=lambda s: "x".join(map(str, s)))
def test_the_worker_checks_what_verify_body_checks(rxw, seg_lens, have,
                                                   bad_seg):
    """crc chained over arbitrary recv sizes and over a head that was
    there before the job: the first bad segment, or -1, as
    `frame_verify_body` says of the same bytes; and the body is the
    sender's."""
    from ceph_tpu.native import frame_native
    rng = random.Random(hash((tuple(seg_lens), str(have), str(bad_seg))))
    blob = bytearray(Frame(Tag.MESSAGE, [rng.randbytes(n) for n in seg_lens]
                           ).encode())
    body = blob[8 + 4 * len(seg_lens):]
    if bad_seg == "last":
        bad_seg = len(seg_lens) - 1
    if bad_seg >= 0:
        # a bit of the segment's bytes, or of its crc where it has none
        at = sum(n + 4 for n in seg_lens[:bad_seg])
        body[at + (seg_lens[bad_seg] // 2 if seg_lens[bad_seg] else 2)] ^= 4
    want = frame_native.verify_body(bytes(body), seg_lens)
    assert want == bad_seg
    if have == "into_the_second":
        have = min(seg_lens[0] + 4 + 2, len(body) - 1)
    w = _Wire(len(body))
    try:
        w.buf[:have] = body[:have]
        assert w.submit(rxw, 11, have, seg_lens) == 0
        _feed(w.a, bytes(body[have:]) + b"NEXT",
              [rng.randrange(1, 100_000) for _ in range(50)])
        (done,) = w.reap(rxw)
        token, got, recvs, cpu_ns, bad, status = done
        assert (token, got, bad, status) == (11, len(body), want, 0)
        assert recvs >= 1 and cpu_ns > 0
        assert w.buf == body
        # not one byte too many: the next frame's are still the socket's
        assert w.b.recv(100) == b"NEXT"
        assert rxw.rxw_jobs() == 0
    finally:
        w.close()


@pytest.mark.parametrize("how", ["eof", "cancel", "cancel_unstarted",
                                 "cancel_done", "no_crc"])
def test_a_job_ends_once_and_lets_go_of_fd_and_buffer(rxw, how):
    n = 300_000
    fds = len(os.listdir("/proc/self/fd"))
    w = _Wire(n)
    try:
        assert w.submit(rxw, 5, 0, [] if how == "no_crc" else [n - 4]) == 0
        if how == "cancel_unstarted":
            assert rxw.rxw_cancel(5) == 0
        else:
            w.a.send(b"z" * 1000)
            while rxw.rxw_progress(5) < 1000:
                pass
        if how == "eof":
            w.a.close()
            (done,) = w.reap(rxw)
            assert done[:3] + done[4:] == (5, 1000, 1, -1, -1)
        elif how == "cancel":
            assert rxw.rxw_cancel(5) == 1000
            assert rxw.rxw_cancel(5) == -1          # once
        elif how in ("cancel_done", "no_crc"):
            _feed(w.a, b"z" * (n - 1000), [n])
            while rxw.rxw_progress(5) >= 0:
                pass
            if how == "cancel_done":
                assert rxw.rxw_cancel(5) == -1      # the ring has it
            (done,) = w.reap(rxw)
            # `z`s are no crc of `z`s: segment 0 is bad, unless none is
            # checked
            assert done[:2] + done[4:] == \
                (5, n, -1 if how == "no_crc" else 0, 0)
        assert rxw.rxw_jobs() == 0 and rxw.rxw_progress(5) == -1
        # the buffer is the caller's: what is written now stays
        w.buf[1000:] = bytes(n - 1000)
        if how not in ("eof", "cancel_done", "no_crc"):
            w.a.send(b"late")
        assert w.buf[1000:] == bytes(n - 1000)
    finally:
        w.close()
    assert len(os.listdir("/proc/self/fd")) == fds


def test_submit_refuses_what_it_cannot_do(rxw):
    import errno
    w = _Wire(1000)
    try:
        assert w.submit(rxw, 1, 1000, []) == -errno.EINVAL     # nothing left
        assert w.submit(rxw, 1, 0, [1] * 5) == -errno.EINVAL   # 5 segments
        fd = w.b.fileno()
        w.b.close()
        lens = (ctypes.c_uint64 * 1)(996)
        assert rxw.rxw_submit(1, fd, ctypes.addressof(w.keep), 0, 1000,
                              lens, 1, w.efd) == -errno.EBADF
        assert rxw.rxw_jobs() == 0
        assert rxw.rxw_stop() == 0
        assert w.submit(rxw, 1, 0, []) == -errno.ESRCH         # no thread
        assert rxw.rxw_cancel(1) == -1 and rxw.rxw_reap(None, 0) == 0
    finally:
        del w.keep
        w.a.close()
        os.close(w.efd)


def test_sixty_jobs_at_once_share_the_thread_and_the_wake_ups(rxw):
    """Completions queue in the ring; one reap call drains them all."""
    n = 200_000
    wires = [_Wire(n) for _ in range(60)]
    efd = wires[0].efd
    rng = random.Random(5)
    bodies = []
    try:
        for i, w in enumerate(wires):
            body = bytes(Frame(Tag.MESSAGE, [rng.randbytes(n - 4)]
                               ).encode())[12:]
            bodies.append(body)
            lens = (ctypes.c_uint64 * 1)(n - 4)
            assert rxw.rxw_submit(100 + i, w.b.fileno(),
                                  ctypes.addressof(w.keep), 0, n, lens, 1,
                                  efd) == 0
        for half in (0, 1):
            for w, body in zip(wires, bodies):
                _feed(w.a, body[half * n // 2:(half + 1) * n // 2], [n])
        got = {}
        while len(got) < len(wires):
            for d in wires[0].reap(rxw):
                got[d[0]] = d
        assert sorted(got) == list(range(100, 160))
        assert all(d[1] == n and d[4] == -1 and d[5] == 0
                   for d in got.values())
        assert all(w.buf == b for w, b in zip(wires, bodies))
    finally:
        for w in wires:
            w.close()


# -- the worker's sends (rxw_submit_tx): a frame's parts and a header buffer ---

_TX_SEGS = {
    "one_segment": lambda r: [r.randbytes(700_000)],
    "two_segments": lambda r: [b'{"type":112,"seq":9}', r.randbytes(524_288)],
    "four_segments": lambda r: [r.randbytes(100_000), b"",
                                r.randbytes(600_001), b"\x7c\xec" * 9],
    "several_live_parts": lambda r: [
        b'{"type":115}', b'{"msgs":[]}',
        [r.randbytes(300_000), memoryview(r.randbytes(300_000)), b"",
         bytearray(r.randbytes(300)), memoryview(r.randbytes(9)).toreadonly()]],
    "empty_segments": lambda r: [b"", b"", b"", b""],
    "no_segment": lambda r: [],
}


class _TxWire:
    """A socket pair and an eventfd, and what a send job is handed: the
    worker sends on `a` (the caller's fd, never closed by the worker)."""

    def __init__(self, sndbuf=None):
        self.a, self.b = socket.socketpair()
        self.a.setblocking(False)
        self.b.setblocking(False)
        if sndbuf:
            self.a.setsockopt(socket.SOL_SOCKET, socket.SO_SNDBUF, sndbuf)
            self.b.setsockopt(socket.SOL_SOCKET, socket.SO_RCVBUF, sndbuf)
        self.efd = os.eventfd(0, os.EFD_NONBLOCK)
        self.keep = []

    def submit(self, lib, token, segments, head=b"", tag=int(Tag.MESSAGE)):
        from ceph_tpu.native import frame_native
        nseg = len(segments)
        seg_parts, ptrs, lens, _n, keep = frame_native._flatten(segments)
        hdr = bytearray(8 + 8 * nseg)
        self.keep.append((head, hdr, keep, segments))
        return lib.rxw_submit_tx(
            token, self.a.fileno(), self.a.fileno(), self.efd, head,
            len(head), frames.MAGIC, tag, nseg, seg_parts, ptrs, lens,
            ctypes.addressof(ctypes.c_char.from_buffer(hdr)))

    def drain(self, n=None, timeout=10.0) -> bytes:
        """What the peer reads: `n` bytes, or all there is for a while."""
        got = bytearray()
        while n is None or len(got) < n:
            if not select.select([self.b], [], [],
                                 0.2 if n is None else timeout)[0]:
                assert n is None, f"only {len(got)} of {n} bytes came"
                break
            chunk = self.b.recv(1 << 20)
            if not chunk:
                break
            got += chunk
        return bytes(got)

    reap = _Wire.reap

    def close(self):
        self.keep.clear()
        self.a.close()
        self.b.close()
        os.close(self.efd)


@pytest.mark.parametrize("head", [b"", b"HEAD" * 500], ids=["bare", "head"])
@pytest.mark.parametrize("shape", list(_TX_SEGS))
def test_the_worker_sends_what_encode_packs(rxw, shape, head):
    """The bytes a peer reads are `Frame.encode()`'s, crcs included,
    behind the head the job was given, and the thread wrote the crcs
    into the job's own header buffer."""
    segs = _TX_SEGS[shape](random.Random(shape))
    want = head + bytes(Frame(Tag.MESSAGE, segs).encode())
    w = _TxWire()
    try:
        assert w.submit(rxw, 21, segs, head) == 0
        got = w.drain(len(want))
        (done,) = w.reap(rxw)
        token, sent, sends, cpu_ns, bad, status = done
        assert (token, sent, bad, status) == (21, len(want), -1, 0)
        assert sends >= 1 and cpu_ns > 0
        assert got == want
        hdr = w.keep[0][1]
        pre = 8 + 4 * len(segs)
        assert bytes(hdr[:pre]) == want[len(head):len(head) + pre]
        assert w.drain() == b"" and rxw.rxw_jobs() == 0
    finally:
        w.close()


def test_a_send_into_a_small_socket_buffer_takes_several_rounds(rxw):
    """4 MiB into a socket that holds a few KiB: the thread waits for
    `EPOLLOUT` between rounds, holds up nobody meanwhile (a second job
    on another socket ends first), and the bytes are the frame's."""
    rng = random.Random(8)
    segs = [b"h", [rng.randbytes(3 << 20), rng.randbytes(1 << 20)]]
    want = bytes(Frame(Tag.MESSAGE, segs).encode())
    small = [rng.randbytes(70_000)]
    slow, fast = _TxWire(sndbuf=4096), _TxWire()
    fast.efd, spare = slow.efd, fast.efd    # one loop's eventfd for both
    try:
        assert slow.submit(rxw, 31, segs) == 0
        while rxw.rxw_progress(31) <= 0:
            pass
        assert 0 < rxw.rxw_progress(31) < len(want) // 2
        assert fast.submit(rxw, 32, small) == 0
        (first,) = slow.reap(rxw)
        assert first[0] == 32 and first[5] == 0
        assert 0 < rxw.rxw_progress(31) < len(want)
        got = slow.drain(len(want))
        (done,) = slow.reap(rxw)
        assert done[:2] + done[4:] == (31, len(want), -1, 0)
        assert done[2] > 4, "one sendmsg took 4 MiB through a 4 KiB buffer"
        assert got == want
        assert fast.drain() == bytes(Frame(Tag.MESSAGE, small).encode())
    finally:
        fast.efd = spare
        slow.close()
        fast.close()


@pytest.mark.parametrize("how", ["cancel", "cancel_unstarted", "cancel_done",
                                 "peer_closes"])
def test_a_send_ends_once_and_lets_go_of_fd_and_parts(rxw, how):
    """A cancel returns only once the thread has let go: not one byte
    leaves after it, the fd stays the caller's (open, and usable), and
    nothing is left with the worker."""
    rng = random.Random(how)
    segs = [rng.randbytes(2 << 20)]
    wire = bytes(Frame(Tag.MESSAGE, segs).encode())
    fds = len(os.listdir("/proc/self/fd"))
    w = _TxWire(sndbuf=4096)
    try:
        if how == "cancel_done":
            w.a.setsockopt(socket.SOL_SOCKET, socket.SO_SNDBUF, 8 << 20)
        assert w.submit(rxw, 41, segs) == 0
        if how == "cancel_unstarted":
            sent = rxw.rxw_cancel(41)
            assert 0 <= sent < len(wire)
        else:
            while rxw.rxw_progress(41) == 0:
                pass
        if how == "cancel":
            sent = rxw.rxw_cancel(41)
            assert 0 < sent < len(wire)
            assert rxw.rxw_cancel(41) == -1             # once
        elif how == "cancel_done":
            got = w.drain(len(wire))
            while rxw.rxw_progress(41) >= 0:
                pass
            assert rxw.rxw_cancel(41) == -1             # the ring has it
            (done,) = w.reap(rxw)
            assert done[:2] + done[4:] == (41, len(wire), -1, 0)
            assert got == wire
        elif how == "peer_closes":
            w.b.close()
            (done,) = w.reap(rxw)
            assert done[0] == 41 and 0 < done[1] < len(wire)
            assert done[5] in (errno.EPIPE, errno.ECONNRESET)
        assert rxw.rxw_jobs() == 0 and rxw.rxw_progress(41) == -1
        if how.startswith("cancel") and how != "cancel_done":
            # what left before the cancel is a prefix of the frame, and
            # the fd is the caller's: its own bytes follow, nothing else
            got = w.drain()
            assert len(got) == sent and got == wire[:sent]
            w.a.send(b"mine")
            assert w.drain() == b"mine"
    finally:
        if how == "peer_closes":
            w.b = socket.socket()
        w.close()
    assert len(os.listdir("/proc/self/fd")) == fds


def test_submit_tx_refuses_what_it_cannot_do(rxw):
    w = _TxWire()
    try:
        assert w.submit(rxw, 1, [b"x"] * 5) == -errno.EINVAL    # 5 segments
        assert rxw.rxw_jobs() == 0
        assert rxw.rxw_stop() == 0
        assert w.submit(rxw, 1, [b"x"]) == -errno.ESRCH         # no thread
        assert rxw.rxw_cancel(1) == -1
    finally:
        w.close()


def test_receives_and_sends_of_sixty_connections_share_the_threads(rxw):
    """Each connection's send job and its peer's receive job at once,
    all through one eventfd: every body arrives whole, crc checked by
    the receiver's pass over what the sender's pass wrote."""
    n = 600_000
    rng = random.Random(6)
    txs = [_TxWire() for _ in range(60)]
    efd = txs[0].efd
    bufs, datas = [], []
    try:
        for i, w in enumerate(txs):
            data = rng.randbytes(n)
            datas.append(data)
            buf = bytearray(n + 4)
            keep = ctypes.c_char.from_buffer(buf)
            bufs.append((buf, keep))
            lens = (ctypes.c_uint64 * 1)(n)
            # the receiver takes the body; the 12 bytes of preamble are
            # read here, as the endpoint's spill would
            w.efd, w.own_efd = efd, w.efd
            assert w.submit(rxw, 1000 + i, [data]) == 0
            pre = b""
            while len(pre) < 12:
                select.select([w.b], [], [], 5.0)
                pre += w.b.recv(12 - len(pre))
            assert rxw.rxw_submit(2000 + i, w.b.fileno(),
                                  ctypes.addressof(keep), 0, n + 4, lens, 1,
                                  efd) == 0
        got = {}
        while len(got) < 2 * len(txs):
            for d in txs[0].reap(rxw):
                got[d[0]] = d
        assert all(got[1000 + i][1] == n + 16 and got[1000 + i][5] == 0
                   for i in range(60))
        assert all(got[2000 + i][1] == n + 4 and got[2000 + i][4] == -1
                   and got[2000 + i][5] == 0 for i in range(60))
        assert all(bytes(b[:n]) == d for (b, _k), d in zip(bufs, datas))
    finally:
        for (buf, keep) in bufs:
            del keep
        bufs.clear()
        for w in txs:
            w.efd = getattr(w, "own_efd", w.efd)
            w.close()

