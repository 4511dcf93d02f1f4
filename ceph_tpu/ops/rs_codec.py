"""Device-side GF(2^8) matrix application — the erasure-code hot path.

TPU-first design: a Reed-Solomon encode/decode over GF(2^8) is a *linear* map
over GF(2) once bytes are viewed as bit vectors. So instead of translating the
reference's table-lookup SIMD kernels (jerasure/ISA-L `ec_encode_data`,
reference src/erasure-code/isa/ErasureCodeIsa.cc:129), we:

  1. expand each of the k input chunks into 8 {0,1} bit-planes,
  2. multiply by the (r*8, k*8) GF(2) *bitmatrix* of the coding matrix with an
     int8 matmul (MXU systolic array, int32 accumulate),
  3. reduce mod 2 and recombine the 8 output bit-planes into bytes (VPU).

Encode and decode are the same kernel with different matrices (decode applies
the inverted survivor submatrix computed on host, cached — the analog of
ErasureCodeIsaTableCache, reference src/erasure-code/isa/ErasureCodeIsaTableCache.h:35).

Everything is shape-bucketed and jit-cached: the OSD/benchmark call sites see
arbitrary chunk sizes; we pad N up to a bucket so XLA compiles a handful of
programs total.
"""
from __future__ import annotations

import collections
import functools

import jax
import jax.numpy as jnp
import numpy as np

from ceph_tpu.ec import gf256

# Pad the byte axis to a multiple of this; keeps the lane dimension aligned to
# TPU (8,128) tiles and bounds the number of distinct compiled programs.
_LANE_QUANTUM = 1024

_BITS = np.arange(8, dtype=np.uint8)


def apply_matrix_np(M: np.ndarray, data: np.ndarray) -> np.ndarray:
    """Ground-truth host encoder: out = M @ data over GF(2^8). (r,k)@(k,N)."""
    return gf256.mat_vec_apply(M, data)


def _bucket_batch(b: int) -> int:
    """Round a stripe-batch count up to the next power of two (min 1) so the
    batched kernel compiles O(log B) programs instead of one per batch size."""
    return 1 << max(0, (b - 1).bit_length())


def _bucket(n: int) -> int:
    """Round n up to a power-of-two multiple of the lane quantum."""
    return max(_LANE_QUANTUM, _bucket_batch(n))


@functools.partial(jax.jit, static_argnames=("r", "k"))
def _apply_bitmatrix_jit(B_i8: jax.Array, data: jax.Array, r: int, k: int) -> jax.Array:
    """data (k, N) uint8, B (r*8, k*8) int8 {0,1} -> (r, N) uint8."""
    n = data.shape[1]
    bits = jnp.asarray(_BITS)
    # (k, 8, N) bit-planes -> (k*8, N) int8
    planes = ((data[:, None, :] >> bits[None, :, None]) & 1).astype(jnp.int8)
    planes = planes.reshape(k * 8, n)
    # GF(2) matmul on the MXU: int8 x int8 -> int32, then mod 2
    acc = jax.lax.dot_general(
        B_i8,
        planes,
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    )
    out_planes = (acc & 1).astype(jnp.uint8).reshape(r, 8, n)
    return jnp.sum(out_planes << bits[None, :, None], axis=1, dtype=jnp.int32).astype(jnp.uint8)


@functools.partial(jax.jit, static_argnames=("r", "k"))
def _apply_bitmatrix_batched_jit(B_i8: jax.Array, data: jax.Array, r: int, k: int) -> jax.Array:
    """data (batch, k, N) uint8 -> (batch, r, N) uint8; one device dispatch
    for a whole batch of stripes (the ECUtil::encode per-stripe loop becomes
    one fused kernel — the batching site named in SURVEY §2.2)."""
    b, _, n = data.shape
    bits = jnp.asarray(_BITS)
    # one program serves encode and every decode of its shape (the
    # bitmatrix is an argument), so the scope can name the rows it
    # computes and not the direction; the plugin names the direction
    # on the host's line of the trace (`rs_encode_r3`, `rs_decode_r1`)
    with jax.named_scope(f"rs_apply_r{r}"):
        planes = ((data[:, :, None, :] >> bits[None, None, :, None]) & 1).astype(jnp.int8)
        planes = planes.reshape(b, k * 8, n)
        acc = jax.lax.dot_general(
            B_i8,
            planes,
            dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.int32,
        )  # (r*8, batch, N)
        out_planes = (acc & 1).astype(jnp.uint8).reshape(r, 8, b, n)
        out = jnp.sum(out_planes << bits[None, :, None, None], axis=1,
                      dtype=jnp.int32).astype(jnp.uint8)
        return out.transpose(1, 0, 2)  # (batch, r, N)


class MatrixCodec:
    """Applies one fixed GF(2^8) matrix (r, k) to byte streams on device.

    Instances are cheap to build; get() memoizes them by matrix content so the
    plugin layer can request the same codec from many call sites. The memo is
    LRU-bounded: long-lived OSDs decoding under churn see many distinct
    erasure patterns, and each codec pins a device bitmatrix buffer (same
    role/bound as ErasureCodeIsaTableCache in the reference).
    """

    _cache: "collections.OrderedDict[bytes, MatrixCodec]" = collections.OrderedDict()
    _CACHE_MAX = 2048
    #: codecs `get` had to build: a bit-matrix expanded on the caller's
    #: thread and put on the device (callers read it before and after)
    misses = 0

    def __init__(self, M: np.ndarray):
        M = np.ascontiguousarray(M, dtype=np.uint8)
        self.M = M
        self.r, self.k = M.shape
        B = gf256.matrix_to_bitmatrix(M)
        self._B = jnp.asarray(B.astype(np.int8))
        # per-device pinned copies for the mesh fan-out: data committed
        # to chip d must meet a bitmatrix committed to d, or every
        # dispatch re-transfers the (uncommitted) matrix over the link
        self._B_dev: dict = {}

    def _bitmatrix_for(self, data) -> jax.Array:
        """The bitmatrix pinned to `data`'s device (single-device
        committed arrays); the default-device copy otherwise (host
        input, or mesh-sharded arrays whose placement jax resolves)."""
        devices = getattr(data, "devices", None)
        if devices is None:
            return self._B
        try:
            ds = devices()
        except Exception:
            return self._B
        if len(ds) != 1:
            return self._B
        dev = next(iter(ds))
        pinned = self._B_dev.get(dev)
        if pinned is None:
            pinned = self._B_dev[dev] = jax.device_put(self._B, dev)
        return pinned

    @classmethod
    def get(cls, M: np.ndarray) -> "MatrixCodec":
        key = np.ascontiguousarray(M, dtype=np.uint8).tobytes() + bytes(M.shape)
        codec = cls._cache.get(key)
        if codec is None:
            codec = cls._cache[key] = cls(M)
            cls.misses += 1
            while len(cls._cache) > cls._CACHE_MAX:
                cls._cache.popitem(last=False)
        else:
            cls._cache.move_to_end(key)
        return codec

    def apply_device(self, data: jax.Array) -> jax.Array:
        """data (k, N) uint8 already on device, N already bucket-aligned."""
        return _apply_bitmatrix_jit(self._bitmatrix_for(data), data,
                                    self.r, self.k)

    def apply_batch_device(self, data: jax.Array) -> jax.Array:
        """data (batch, k, N) uint8 on device -> (batch, r, N).

        Both the batch and lane axes are bucket-padded (batch to a power of
        two, N to _bucket) so the expensive matmul program is compiled once
        per bucket, not once per caller shape; the pad/slice wrappers are
        trivial programs. Mirrors MatrixCodec.apply (ADVICE r1).
        """
        b, _, n = data.shape
        bb, nb = _bucket_batch(b), _bucket(n)
        B_dev = self._bitmatrix_for(data)
        if (bb, nb) != (b, n):
            data = jnp.pad(data, ((0, bb - b), (0, 0), (0, nb - n)))
        out = _apply_bitmatrix_batched_jit(B_dev, data, self.r, self.k)
        if (bb, nb) != (b, n):
            out = out[:b, :, :n]
        return out

    def apply(self, data: np.ndarray) -> np.ndarray:
        """Host-convenience path: pads, ships to device, returns numpy (r, N)."""
        data = np.ascontiguousarray(data, dtype=np.uint8)
        k, n = data.shape
        if k != self.k:
            raise ValueError(f"expected {self.k} input chunks, got {k}")
        nb = _bucket(n)
        if nb != n:
            padded = np.zeros((k, nb), dtype=np.uint8)
            padded[:, :n] = data
            data = padded
        out = self.apply_device(jnp.asarray(data))
        return np.asarray(out)[:, :n]


# ---------------------------------------------------------------------------
# Decode support: survivor-submatrix inversion, host-side + cached
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=4096)
def _recovery_matrix_cached(coding_bytes: bytes, k: int, m: int,
                            avail: tuple[int, ...], want: tuple[int, ...]) -> bytes:
    coding = np.frombuffer(coding_bytes, dtype=np.uint8).reshape(m, k)
    gen = np.vstack([np.eye(k, dtype=np.uint8), coding])  # (k+m, k) generator
    sub = gen[list(avail), :]  # (k, k) rows we have
    inv = gf256.mat_invert(sub)  # chunk j = inv[j] . avail_data
    rows = []
    for w in want:
        if w < k:
            rows.append(inv[w])
        else:
            # parity chunk = coding row applied to recovered data chunks
            rows.append(gf256.mat_mul(coding[w - k : w - k + 1, :], inv)[0])
    return np.asarray(rows, dtype=np.uint8).tobytes()


def recovery_matrix(coding: np.ndarray, avail: tuple[int, ...],
                    want: tuple[int, ...]) -> np.ndarray:
    """Matrix R (len(want), k) with chunk[w] = R @ data[avail] over GF(2^8).

    `coding` is the (m, k) parity matrix; chunk ids 0..k-1 are data chunks and
    k..k+m-1 parity chunks. `avail` must list exactly k available chunk ids in
    the order their data will be stacked.
    """
    coding = np.ascontiguousarray(coding, dtype=np.uint8)
    m, k = coding.shape
    if len(avail) != k:
        raise ValueError(f"need exactly {k} available chunks, got {len(avail)}")
    raw = _recovery_matrix_cached(coding.tobytes(), k, m, tuple(avail), tuple(want))
    return np.frombuffer(raw, dtype=np.uint8).reshape(len(want), k)
