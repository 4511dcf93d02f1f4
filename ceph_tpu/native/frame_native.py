"""ctypes wrapper over the native msgr2 frame codec (native/ec_native.cc
`frame_pack` / `frame_crcs` / `frame_verify_body`).

One C call does a whole frame's codec work in place of the per-segment
Python/ctypes loop frames.py otherwise runs: `pack` builds the preamble,
copies every segment and crcs it into one wire blob (small frames, and
whatever an onwire transform wraps); `crcs` builds the preamble and every
segment's crc and copies nothing (frames sent by reference, see
`Frame.encode_parts`); `verify_body` checks a received body's crcs. The
calls release the GIL (plain ctypes CDLL semantics), and they stay on
the event loop all the same: moving `pack` and `verify_body` of frames
of 64 KiB and more to a helper thread (`run_in_executor`) read 8% FEWER
ops a second in `rb4m_seqread` (CPU container, PR 28's issue) — the GIL
hand-offs and the extra loop turns cost more than the overlap frees.
That was a hop a FRAME: a Python worker thread, two hand-overs of the
interpreter's lock and a `call_soon_threadsafe` for 50-80 us of crc.
What did pay (PR 52) is a hand-over a BODY: a body of
`msg/rxworker.py`'s `LINE` or more is received whole, and its crcs
checked as it arrives, by a native thread that never takes the
interpreter's lock; the loop pays one submit and one reap for a
receive and a crc pass of half a millisecond and more, and
`verify_body` is then not called for that frame at all. The send side
followed (PR 54): for a plain-crc frame of that line or more `crcs` is
not called either; `_flatten` below gives its parts to the same worker
(`rxworker.submit_tx`), which computes the crcs as it sends. Bodies and
frames under the line, and every `pack`, are as they were.
The wire layout is bit-identical to the pure-Python path; frames.py
probes `available()` at import and silently keeps the Python fallback
when the library (or a compiler to build it) is missing.

Segments are bytes-likes or LISTS of bytes-likes (scatter segments, the
sub-op batch envelope's concatenated message datas): parts are flattened
into one pointer array, so a scatter segment is crc-chained (and, in
`pack`, copied exactly once) without an intermediate join.

This wrapper is on the per-frame hot path, so pointer extraction avoids
numpy where it can: bytes ride ctypes' native c_char_p conversion
(zero-copy, ~0.5µs) and writable buffers go through c_char.from_buffer
(~0.4µs); only READ-ONLY non-bytes buffers (rx memoryview windows) pay
the np.frombuffer fallback (~2.7µs) — profiled, the difference was ~10µs
a frame, real money at tens of thousands of frames per second.
"""
from __future__ import annotations

import ctypes

_lib = None
_checked = False

_c_char = ctypes.c_char
_c_char_p = ctypes.c_char_p
_c_u64 = ctypes.c_uint64
_addressof = ctypes.addressof
_cast = ctypes.cast


def available() -> bool:
    """True when the native library loads and carries the frame codec.
    Never raises: callers use this as the import-time probe."""
    global _lib, _checked
    if _checked:
        return _lib is not None
    _checked = True
    try:
        from ceph_tpu import native
        lib = native.load()
    except Exception:
        return False
    if not hasattr(lib, "frame_crcs"):
        return False
    _lib = lib
    return True


def _fill_ptr(ptrs, i, part, keep) -> None:
    """Point ptrs[i] at `part`'s buffer without copying."""
    if type(part) is bytes:
        ptrs[i] = part              # ctypes borrows the bytes' pointer
        keep.append(part)
        return
    try:
        c = _c_char.from_buffer(part)       # writable buffers
    except (TypeError, ValueError, BufferError):
        import numpy as np
        arr = np.frombuffer(part, dtype=np.uint8)   # read-only views
        keep.append(arr)
        ptrs[i] = _cast(arr.ctypes.data, _c_char_p)
        return
    keep.append(c)
    ptrs[i] = _cast(_addressof(c), _c_char_p)


def _flatten(segments: list):
    """(parts a segment, part pointers, part lengths, payload bytes,
    keep-alives) of a frame's segments for the two native calls:
    scatter segments flattened into one pointer array."""
    nseg = len(segments)
    seg_parts = (_c_u64 * nseg)() if nseg else None
    flat: list = []
    for i, seg in enumerate(segments):
        if isinstance(seg, (list, tuple)):
            seg_parts[i] = len(seg)
            flat.extend(seg)
        else:
            seg_parts[i] = 1
            flat.append(seg)
    n = len(flat)
    ptrs = (_c_char_p * n)() if n else None
    lens = (_c_u64 * n)() if n else None
    keep: list = []
    payload = 0
    for i, part in enumerate(flat):
        ln = len(part)
        lens[i] = ln
        payload += ln
        if ln:
            _fill_ptr(ptrs, i, part, keep)
    return seg_parts, ptrs, lens, payload, keep


def pack(magic: int, tag: int, segments: list) -> bytearray:
    """Wire form of one frame: preamble + segments with trailing crcs,
    built in a single native call. A segment may be a list/tuple of
    parts (scatter segment); its crc chains across the parts."""
    nseg = len(segments)
    seg_parts, ptrs, lens, payload, _keep = _flatten(segments)
    total = 8 + 8 * nseg + payload
    out = bytearray(total)
    wrote = _lib.frame_pack(
        magic, tag, nseg, seg_parts, ptrs, lens,
        _addressof(_c_char.from_buffer(out)))
    assert wrote == total, (wrote, total)
    return out


def crcs(magic: int, tag: int, segments: list) -> bytearray:
    """What a frame sent by reference needs besides its segments, in a
    single native call that copies nothing: the preamble (8 + 4*nseg
    bytes) followed by each segment's crc (4 bytes each, chained across
    a scatter segment's parts). The caller slices it and sends
    [preamble, segment 0's parts, crc 0, ...]."""
    nseg = len(segments)
    seg_parts, ptrs, lens, _payload, _keep = _flatten(segments)
    out = bytearray(8 + 8 * nseg)
    wrote = _lib.frame_crcs(
        magic, tag, nseg, seg_parts, ptrs, lens,
        _addressof(_c_char.from_buffer(out)))
    assert wrote == len(out), (wrote, len(out))
    return out


def verify_body(body, seg_lens: list[int]) -> int:
    """Per-segment crc verification of a received frame body (runs of
    [seg bytes | crc u32]): -1 = all good, else the index of the first
    bad segment. The caller validated the preamble (and with it the
    lengths) already."""
    n = len(seg_lens)
    if not n:
        return -1
    lens = (_c_u64 * n)(*seg_lens)
    if type(body) is bytes:
        return _lib.frame_verify_body(body, lens, n)
    try:
        addr = _addressof(_c_char.from_buffer(body))
    except (TypeError, ValueError, BufferError):
        import numpy as np
        arr = np.frombuffer(body, dtype=np.uint8)
        return _lib.frame_verify_body(arr.ctypes.data, lens, n)
    return _lib.frame_verify_body(addr, lens, n)
