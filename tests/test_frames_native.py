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
import os  # noqa: E402
import select  # noqa: E402
import socket  # noqa: E402


@pytest.fixture
def rxw():
    """The library with the worker's thread running, and stopped after."""
    _native_or_skip()
    from ceph_tpu.msg import rxworker
    if not rxworker.available():
        pytest.skip("the library has no receive worker (not Linux)")
    assert not rxworker._ports, "an earlier test left the worker in use"
    lib = rxworker._lib
    assert lib.rxw_start() == 0 and lib.rxw_start() == 0    # idempotent
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
