"""crc32c over fixed-size blocks as a TPU bitmatrix matmul.

TPU-first design: CRC32C is GF(2)-linear in the message bits for a fixed
block length and seed — crc(m) = L @ m_bits  XOR  const, where L is a
(block_bits, 32) bitmatrix and const = crc(seed, zero block). So a batch of
blocks becomes ONE int8 matmul on the MXU:

    blocks (B, N) uint8 -> bitplanes (B, N*8) int8 @ L (N*8, 32) -> &1
    -> packed (B,) uint32

This replaces the reference's byte-serial table/PCLMUL kernels
(src/common/crc32c.cc:17) for the BlueStore Checksummer batch shape
(per-blob 4 KiB csum blocks, src/common/Checksummer.h:195-234,
src/os/bluestore/bluestore_types.cc:814,840) — thousands of independent
blocks per write batch, exactly what the MXU wants.

L is built on host with the standard crc-combine algebra (the zlib
crc32_combine technique): a 32x32 "advance one zero byte" operator Z, its
powers give each byte position's contribution operator; column (p, b) of L
is Z^(N-1-p) @ bits(table0[1<<b]). Seed convention matches ceph_crc32c
(raw LFSR, caller passes seed, default -1, no final xor).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

_POLY = 0x82F63B78  # reflected Castagnoli


@functools.lru_cache(maxsize=1)
def _table0() -> np.ndarray:
    t = np.zeros(256, dtype=np.uint32)
    for i in range(256):
        c = i
        for _ in range(8):
            c = (c >> 1) ^ _POLY if c & 1 else c >> 1
        t[i] = c
    return t


def _bits32(x: int) -> np.ndarray:
    return ((int(x) >> np.arange(32)) & 1).astype(np.uint8)


@functools.lru_cache(maxsize=1)
def _zero_byte_op() -> np.ndarray:
    """32x32 GF(2) matrix Z with Z @ bits(c) = bits(step(c, 0))."""
    t = _table0()
    Z = np.zeros((32, 32), dtype=np.uint8)
    for i in range(32):
        c = 1 << i
        nxt = int(t[c & 0xFF]) ^ (c >> 8)
        Z[:, i] = _bits32(nxt)
    return Z


def _gf2_matmul(A: np.ndarray, B: np.ndarray) -> np.ndarray:
    return (A.astype(np.uint32) @ B.astype(np.uint32) & 1).astype(np.uint8)


@functools.lru_cache(maxsize=8)
def crc_bitmatrix(block_size: int) -> np.ndarray:
    """(block_size*8, 32) uint8 bitmatrix L: crc_bits = m_bits @ L.

    m_bits layout: byte p contributes bits (p*8 + b), b = little-endian bit
    index within the byte (matches the uint8 >> b bitplane extraction).
    """
    t = _table0()
    Z = _zero_byte_op()
    step_cols = np.stack([_bits32(int(t[1 << b])) for b in range(8)],
                         axis=1)  # (32, 8)
    L = np.zeros((block_size * 8, 32), dtype=np.uint8)
    op = np.eye(32, dtype=np.uint8)  # Z^(N-1-p) for p = N-1
    for p in range(block_size - 1, -1, -1):
        L[p * 8:(p + 1) * 8, :] = _gf2_matmul(op, step_cols).T
        if p:
            op = _gf2_matmul(op, Z)
    return L


@functools.lru_cache(maxsize=8)
def _seed_const(block_size: int, seed: int) -> int:
    """crc of a zero block with the given starting crc (the affine const)."""
    t = _table0()
    c = seed & 0xFFFFFFFF
    for _ in range(block_size):
        c = int(t[c & 0xFF]) ^ (c >> 8)
    return c


#: the fewest rows a batch is padded to: one shard of a 4 MiB object at
#: k=8 (512 KiB of 4 KiB blocks); smaller programs would only add shapes
MIN_BATCH_BLOCKS = 128


def batch_rows(b: int) -> int:
    """The rows a batch of `b` blocks is run at: a power of two (the
    codec's rule, `rs_codec._bucket_batch`) and at least
    MIN_BATCH_BLOCKS, so that a few programs serve every batch size."""
    return max(MIN_BATCH_BLOCKS, 1 << max(0, (b - 1).bit_length()))


@functools.partial(jax.jit, static_argnames=("block_size",))
def _crc_blocks_jit(L_i8: jax.Array, const: jax.Array, blocks: jax.Array,
                    block_size: int) -> jax.Array:
    b = blocks.shape[0]
    bits = jnp.arange(8, dtype=jnp.uint8)
    with jax.named_scope(f"crc32c_b{block_size}"):
        planes = ((blocks[:, :, None] >> bits[None, None, :]) & 1) \
            .astype(jnp.int8)
        planes = planes.reshape(b, block_size * 8)
        acc = jax.lax.dot_general(planes, L_i8, (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.int32)  # (B, 32)
        crc_bits = (acc & 1).astype(jnp.uint32)
        weights = jnp.uint32(1) << jnp.arange(32, dtype=jnp.uint32)
        return jnp.sum(crc_bits * weights[None, :], axis=1,
                       dtype=jnp.uint32) ^ const


class Crc32cDevice:
    """Batched device crc32c for one (block_size, seed) shape.

    A batch runs at `batch_rows` of its size, through a program compiled
    ahead of its first use for (rows, device) and kept here: `warm`
    compiles, or loads from the persistent cache, every program a batch
    of up to `most` blocks can need, so that a server has them before
    it serves; a shape that was not warmed compiles at its first batch.
    """

    def __init__(self, block_size: int, seed: int = 0xFFFFFFFF):
        self.block_size = block_size
        self.seed = seed & 0xFFFFFFFF
        self._L = crc_bitmatrix(block_size).astype(np.int8)
        self._const = np.uint32(_seed_const(block_size, self.seed))
        self._on: dict = {}         # device -> (L, const) committed to it
        self._programs: dict = {}   # (rows, device) -> compiled program

    def _program(self, rows: int, device):
        """The program for `rows` blocks on `device`, and its constants
        there. Two threads that miss together both compile: benign."""
        consts = self._on.get(device)
        if consts is None:
            consts = self._on[device] = (jax.device_put(self._L, device),
                                         jax.device_put(self._const, device))
        prog = self._programs.get((rows, device))
        if prog is None:
            shape = jax.ShapeDtypeStruct(
                (rows, self.block_size), jnp.uint8,
                sharding=jax.sharding.SingleDeviceSharding(device))
            prog = self._programs[(rows, device)] = _crc_blocks_jit.lower(
                *consts, shape, block_size=self.block_size).compile()
        return prog, consts

    def warm(self, device, most: int) -> None:
        """Have every program ready that a batch of 1..`most` blocks on
        `device` can run; nothing is executed."""
        rows = MIN_BATCH_BLOCKS
        while rows <= batch_rows(most):
            self._program(rows, device)
            rows *= 2

    def _run(self, dev: jax.Array) -> jax.Array:
        """`dev`: (batch_rows(b), block_size) uint8 on one device."""
        device = next(iter(dev.devices()))
        prog, consts = self._program(dev.shape[0], device)
        # the device's line of a trace names the scope; this names the
        # launch on the host's line, as `rs_encode_r3` does an encode
        with jax.profiler.TraceAnnotation(f"crc32c_b{self.block_size}"):
            return prog(*consts, dev)

    def __call__(self, blocks):
        """blocks (B, block_size) uint8 -> (B,) uint32. A host array is
        padded on the host and comes back as numpy; a device array that
        already has `batch_rows` rows (the offload service stages its
        batches so) runs as it is, any other is padded on the device."""
        if blocks.ndim != 2 or blocks.shape[1] != self.block_size:
            raise ValueError(f"expected (B, {self.block_size}), "
                             f"got {blocks.shape}")
        b = blocks.shape[0]
        rows = batch_rows(b)
        if isinstance(blocks, jax.Array):
            if len(blocks.devices()) != 1:
                blocks = jax.device_put(blocks, jax.local_devices()[0])
            if rows != b:
                blocks = jnp.pad(blocks, ((0, rows - b), (0, 0)))
            out = self._run(blocks)
            return out if rows == b else out[:b]
        if rows == b:
            padded = np.ascontiguousarray(blocks, dtype=np.uint8)
        else:
            padded = np.zeros((rows, self.block_size), dtype=np.uint8)
            padded[:b] = blocks
        return np.asarray(self._run(jax.device_put(
            padded, jax.local_devices()[0])))[:b]


@functools.lru_cache(maxsize=8)
def get_device_crc(block_size: int, seed: int = 0xFFFFFFFF) -> Crc32cDevice:
    return Crc32cDevice(block_size, seed)
