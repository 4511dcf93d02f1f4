"""ctypes loader for the native C++ runtime kernels (native/*.cc).

Builds `libec_native.<digest>.so` on first use with g++ (named after its
source's sha256) — the framework's analog of the reference's vendored SIMD
libraries, but compiled from our own sources. Import `ec_native` for the GF(2^8) host codec
and `crc32c` helpers; both raise NativeUnavailable cleanly if no compiler
exists so pure-Python/JAX paths can fall back.
"""
from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading

_HERE = os.path.dirname(os.path.abspath(__file__))
_REPO = os.path.dirname(os.path.dirname(_HERE))
_SRC = os.path.join(_REPO, "native", "ec_native.cc")
_BUILD_DIR = os.path.join(_REPO, "native", "_build")

_lock = threading.Lock()
_lib = None


class NativeUnavailable(RuntimeError):
    pass


def _build() -> str:
    """The library is named after the digest of its source, so one that
    exists is current: a copied or unpacked tree does not keep mtimes
    in order, and they cannot tell a stale `.so` from a fresh one."""
    os.makedirs(_BUILD_DIR, exist_ok=True)
    with open(_SRC, "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()[:16]
    so = os.path.join(_BUILD_DIR, f"libec_native.{digest}.so")
    if os.path.exists(so):
        return so
    # build beside the target and rename into place: worker processes
    # that race the first import each install a complete library
    tmp = f"{so}.{os.getpid()}"
    cmd = ["g++", "-O3", "-shared", "-fPIC", "-std=c++17", "-pthread", _SRC,
           "-o", tmp]
    try:
        subprocess.run(cmd, check=True, capture_output=True, timeout=120)
    except (OSError, subprocess.SubprocessError) as e:
        detail = getattr(e, "stderr", b"") or b""
        raise NativeUnavailable(
            f"building {so} failed: {e} {detail.decode(errors='replace')}") from e
    os.replace(tmp, so)
    return so


def declare_rxw(lib) -> None:
    """The socket worker's entry points (native/ec_native.cc, `rxw_*`)
    on a handle of the library: `load()`'s, or msg/rxworker.py's second
    one, whose calls keep the interpreter's lock."""
    u64p = ctypes.POINTER(ctypes.c_uint64)
    lib.rxw_submit.restype = ctypes.c_int
    lib.rxw_submit.argtypes = [
        ctypes.c_uint64, ctypes.c_int, ctypes.c_void_p, ctypes.c_uint64,
        ctypes.c_uint64, u64p, ctypes.c_int, ctypes.c_int]
    lib.rxw_submit_tx.restype = ctypes.c_int
    lib.rxw_submit_tx.argtypes = [      # ..., then frame_crcs' own
        ctypes.c_uint64, ctypes.c_int, ctypes.c_int, ctypes.c_int,
        ctypes.c_void_p, ctypes.c_uint64,
        ctypes.c_uint32, ctypes.c_uint32, ctypes.c_int,
        u64p, ctypes.POINTER(ctypes.c_char_p), u64p, ctypes.c_void_p]
    lib.rxw_cancel.restype = ctypes.c_int64
    lib.rxw_cancel.argtypes = [ctypes.c_uint64]
    lib.rxw_progress.restype = ctypes.c_int64
    lib.rxw_progress.argtypes = [ctypes.c_uint64]
    lib.rxw_reap.restype = ctypes.c_int     # six a completion, two signed
    lib.rxw_reap.argtypes = [ctypes.POINTER(ctypes.c_int64), ctypes.c_int]
    lib.rxw_start.restype = ctypes.c_int
    lib.rxw_start.argtypes = [ctypes.c_int]
    for name in ("rxw_stop", "rxw_running", "rxw_jobs"):
        getattr(lib, name).restype = ctypes.c_int
        getattr(lib, name).argtypes = []
    lib.rxw_forked.restype = None
    lib.rxw_forked.argtypes = []


def load() -> ctypes.CDLL:
    global _lib
    with _lock:
        if _lib is None:
            lib = ctypes.CDLL(_build())
            u8p = ctypes.POINTER(ctypes.c_uint8)
            u32p = ctypes.POINTER(ctypes.c_uint32)
            lib.gf256_encode.argtypes = [u8p, ctypes.c_int, ctypes.c_int,
                                         u8p, u8p, u8p, ctypes.c_size_t]
            lib.gf256_region_xor.argtypes = [u8p, u8p, ctypes.c_size_t]
            lib.crc32c.restype = ctypes.c_uint32
            lib.crc32c.argtypes = [ctypes.c_uint32, u8p, ctypes.c_size_t]
            lib.crc32c_blocks.argtypes = [u8p, ctypes.c_size_t,
                                          ctypes.c_size_t, ctypes.c_uint32,
                                          u32p]
            lib.planes_from_stripes.restype = None
            lib.planes_from_stripes.argtypes = [
                u8p, ctypes.c_size_t, ctypes.c_size_t, ctypes.c_size_t,
                ctypes.c_size_t, ctypes.c_size_t, u8p]
            lib.ec_native_crc32c_sw.restype = ctypes.c_uint32
            lib.ec_native_crc32c_sw.argtypes = lib.crc32c.argtypes
            lib.ec_native_crc32c_impl.restype = ctypes.c_char_p
            # msgr2 frame codec (present in rebuilt libraries; a stale
            # .so predating it is never picked: the name carries the source
            # digest)
            u64p = ctypes.POINTER(ctypes.c_uint64)
            if hasattr(lib, "frame_pack"):
                lib.frame_pack.restype = ctypes.c_uint64
                lib.frame_pack.argtypes = [
                    ctypes.c_uint32, ctypes.c_uint32, ctypes.c_int,
                    u64p, ctypes.POINTER(ctypes.c_char_p), u64p,
                    ctypes.c_void_p]
                lib.frame_crcs.restype = ctypes.c_uint64
                lib.frame_crcs.argtypes = lib.frame_pack.argtypes
                lib.frame_verify_body.restype = ctypes.c_int
                lib.frame_verify_body.argtypes = [ctypes.c_void_p, u64p,
                                                  ctypes.c_int]
            # the messenger's socket worker (Linux only)
            if hasattr(lib, "rxw_submit"):
                declare_rxw(lib)
            lib.ec_native_have_avx2.restype = ctypes.c_int
            lib.ec_native_have_sse42.restype = ctypes.c_int
            _lib = lib
    return _lib
