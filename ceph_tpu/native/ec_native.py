"""numpy-facing wrappers over the native C++ kernels.

`encode(M, data, out)` is the host-CPU equivalent of the reference isa
plugin's `ec_encode_data` call (src/erasure-code/isa/ErasureCodeIsa.cc:129):
split-nibble SIMD multiply tables, precomputed per coefficient. Used as the
benchmark's host baseline and as the no-accelerator fallback codec.
"""
from __future__ import annotations

import ctypes
import functools

import numpy as np

from ceph_tpu import native
from ceph_tpu.ec import gf256

_u8p = ctypes.POINTER(ctypes.c_uint8)
_u32p = ctypes.POINTER(ctypes.c_uint32)


def _ptr(a: np.ndarray):
    return a.ctypes.data_as(_u8p)


@functools.lru_cache(maxsize=1)
def _split_tables() -> np.ndarray:
    """(256, 32) uint8: row c = [c*v for v<16] + [c*(v<<4) for v<16]."""
    t = np.zeros((256, 32), dtype=np.uint8)
    lo = np.arange(16, dtype=np.uint8)
    for c in range(256):
        t[c, :16] = gf256.GF_MUL_TABLE[c, lo]
        t[c, 16:] = gf256.GF_MUL_TABLE[c, lo << 4]
    return np.ascontiguousarray(t)


def encode(M: np.ndarray, data: np.ndarray, out: np.ndarray) -> np.ndarray:
    """out(m,n) = M(m,k) @ data(k,n) over GF(2^8), via the C++ kernel."""
    lib = native.load()
    M = np.ascontiguousarray(M, dtype=np.uint8)
    data = np.ascontiguousarray(data, dtype=np.uint8)
    m, k = M.shape
    kd, n = data.shape
    if kd != k:
        raise ValueError(f"matrix expects {k} chunks, data has {kd}")
    if out.shape != (m, n) or out.dtype != np.uint8 or not out.flags.c_contiguous:
        raise ValueError("out must be C-contiguous uint8 of shape (m, n)")
    lib.gf256_encode(_ptr(M), m, k, _ptr(_split_tables()), _ptr(data),
                     _ptr(out), n)
    return out


def region_xor(src: np.ndarray, dst: np.ndarray) -> np.ndarray:
    lib = native.load()
    src = np.ascontiguousarray(src, dtype=np.uint8)
    if dst.shape != src.shape or not dst.flags.c_contiguous:
        raise ValueError("dst must match src and be contiguous")
    lib.gf256_region_xor(_ptr(src), _ptr(dst), src.size)
    return dst


_crc_fast = None


def crc32c(data: bytes | np.ndarray, crc: int = 0xFFFFFFFF) -> int:
    """Castagnoli CRC with ceph's seed convention (crc32c(-1) default).

    bytes-likes go straight through as char* — the numpy round trip
    (frombuffer + ctypes cast) cost ~25us per call and showed up on
    every message frame (profiled on the cluster bench)."""
    global _crc_fast
    if type(data).__name__ == "GuardedView":
        # sanitizer-guarded rx view: checked unwrap at the native
        # boundary (lazy import — native must not hard-depend on utils)
        from ceph_tpu.utils.sanitizer import unwrap
        data = unwrap(data)
    if _crc_fast is None:
        lib = native.load()
        fast = ctypes.CFUNCTYPE(ctypes.c_uint32, ctypes.c_uint32,
                                ctypes.c_char_p, ctypes.c_size_t)(
            ctypes.cast(lib.crc32c, ctypes.c_void_p).value)
        _crc_fast = fast
    if isinstance(data, bytes):
        return int(_crc_fast(crc, data, len(data)))
    if isinstance(data, (bytearray, memoryview)):
        # zero-copy: view the buffer instead of materializing bytes —
        # shard replies now arrive as memoryviews (ec_util zero-copy
        # assemble) and a bytes() round trip here would give the copy
        # right back. Strided views (which np.frombuffer rejects) keep
        # the old materializing contract.
        if isinstance(data, memoryview) and not data.c_contiguous:
            b = bytes(data)
            return int(_crc_fast(crc, b, len(b)))
        arr = np.frombuffer(data, dtype=np.uint8)
        return int(native.load().crc32c(ctypes.c_uint32(crc), _ptr(arr),
                                        arr.size))
    arr = np.ascontiguousarray(data, dtype=np.uint8).reshape(-1)
    return int(native.load().crc32c(ctypes.c_uint32(crc), _ptr(arr),
                                    arr.size))


def crc32c_impl() -> str:
    """The kernel `crc32c` runs on this host: "hw3" (the crc32
    instruction, three interleaved chains) or "sw" (slice-by-8 tables).
    It is chosen from the cpu's features alone, so it engages always or
    never."""
    return native.load().ec_native_crc32c_impl().decode()


def crc32c_sw(data: bytes | np.ndarray, crc: int = 0xFFFFFFFF) -> int:
    """`crc32c` through the table kernel whatever the host has: what
    the tests hold the dispatched kernel to."""
    arr = np.frombuffer(data, dtype=np.uint8)
    return int(native.load().ec_native_crc32c_sw(
        ctypes.c_uint32(crc), _ptr(arr), arr.size))


def crc32c_blocks(data: np.ndarray, block_size: int,
                  seed: int = 0xFFFFFFFF) -> np.ndarray:
    """Per-block CRCs of a (nblocks*block_size,) or (nblocks, block_size)
    buffer — the Checksummer batch path."""
    lib = native.load()
    arr = np.ascontiguousarray(data, dtype=np.uint8).reshape(-1)
    if arr.size % block_size:
        raise ValueError("buffer not a multiple of block_size")
    nb = arr.size // block_size
    out = np.zeros(nb, dtype=np.uint32)
    lib.crc32c_blocks(_ptr(arr), nb, block_size, ctypes.c_uint32(seed),
                      out.ctypes.data_as(_u32p))
    return out


def planes_from_stripes(stripes: np.ndarray, first: int, count: int,
                        out: np.ndarray) -> None:
    """Planes `first` .. `first + count` of (S, n, C) uint8 `stripes`
    (plane i is `stripes[:, i, :]`: shard i's chunk of every stripe) made
    contiguous, one after another, in `out` (count * S * C bytes): the
    per-shard buffers of an erasure-coded write, all in one call that
    runs without the GIL."""
    if stripes.dtype != np.uint8 or stripes.ndim != 3 \
            or not stripes.flags.c_contiguous:
        raise ValueError("stripes: a C-contiguous (S, n, C) uint8 array")
    S, n, C = stripes.shape
    if not 0 <= first <= first + count <= n:
        raise ValueError(f"planes {first}..{first + count} of {n}")
    if out.dtype != np.uint8 or not out.flags.c_contiguous \
            or not out.flags.writeable or out.size != count * S * C:
        raise ValueError(f"out: {count * S * C} contiguous writable bytes")
    native.load().planes_from_stripes(_ptr(stripes), S, n, C, first, count,
                                      _ptr(out))


def available() -> bool:
    try:
        native.load()
        return True
    except native.NativeUnavailable:
        return False
