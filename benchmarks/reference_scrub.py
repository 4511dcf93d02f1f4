"""The plain reference for deep scrub: what every shard of a PG's objects
must report, and who is wrong when one does not.

It imports nothing of the program. The shards are `benchmarks.reference`'s
(`expected_shards`: the value striped over k shards and its
`reed_sol_van` parity). The digest is crc32c by its bitwise definition,
as upstream's `ceph_crc32c` computes it: the Castagnoli polynomial
reflected (0x82F63B78), the register seeded with -1, one bit shifted out
a step, and no final xor. No table is built; numpy only runs the same
eight steps a byte for all blocks at once, block after block's byte in
lockstep, so that the sampled shards of a run (tens of MiB) take
seconds.

A deep scrub of an EC pool re-reads every shard and checks each
`chunk`-sized block against the digest stored beside it at write time
(the per-shard hinfo of upstream's ECBackend). So for an object the
reference gives: the bytes each shard holds, and the digest of each of
its blocks (`scrub_map`); and for the blobs the OSDs hold, which shards
differ (`verdict`), which is what a round must find, and repair.
"""
from __future__ import annotations

import numpy as np

from benchmarks.reference import expected_shards

POLY = 0x82F63B78       # x^32 + x^28 + ... + 1 (Castagnoli), reflected
SEED = 0xFFFFFFFF


def crc32c(data: bytes, seed: int = SEED) -> int:
    """One buffer, bit by bit."""
    c = seed
    for byte in data:
        c ^= byte
        for _ in range(8):
            c = (c >> 1) ^ POLY if c & 1 else c >> 1
    return c


def block_digests(shard, block: int) -> np.ndarray:
    """(n,) uint32: the crc32c of each `block` bytes of `shard` (bytes
    or a uint8 array whose size is a multiple of `block`), every block
    from the seed. The loop is `crc32c`'s, over byte positions; the
    blocks advance together."""
    buf = np.frombuffer(shard, dtype=np.uint8) if isinstance(
        shard, (bytes, bytearray, memoryview)) else np.asarray(
            shard, dtype=np.uint8)
    if buf.size % block:
        raise ValueError(f"block_digests: {buf.size} bytes are not whole "
                         f"blocks of {block}")
    blocks = buf.reshape(-1, block)
    c = np.full(blocks.shape[0], SEED, dtype=np.uint32)
    poly = np.uint32(POLY)
    for p in range(block):
        c ^= blocks[:, p]
        for _ in range(8):
            c = (c >> 1) ^ (poly & (0 - (c & 1)))
    return c


def scrub_map(objects: dict[str, bytes], k: int, m: int,
              chunk: int) -> dict[str, dict]:
    """name -> {"size": the bytes every shard of the object holds,
    "digests": (k+m, size/chunk) uint32, the digest of each block of
    each shard}: what the k+m members of the PG must report."""
    out = {}
    for name, value in objects.items():
        shards = expected_shards(value, k, m, chunk)
        out[name] = {"size": int(shards.shape[1]),
                     "digests": np.stack([block_digests(row, chunk)
                                          for row in shards])}
    return out


def verdict(expected: dict, blobs: dict[int, bytes], chunk: int) -> list[int]:
    """The shards that differ from `expected` (one entry of
    `scrub_map`), given shard index -> the blob its OSD holds: a shard
    that is missing, of the wrong size, or with a block whose digest is
    not the expected one. Empty: the object is clean."""
    bad = []
    for shard in range(expected["digests"].shape[0]):
        blob = blobs.get(shard)
        if blob is None or len(blob) != expected["size"] \
                or not np.array_equal(block_digests(blob, chunk),
                                      expected["digests"][shard]):
            bad.append(shard)
    return bad
