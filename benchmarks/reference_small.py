"""The plain reference for a small whole-object write on an erasure
pool: what a `write_full` of n bytes must leave on k+m shards, and the
least it must move, reckoned from (n, k, m, chunk) alone.

It imports nothing of the program. The shards' bytes are
`reference.expected_shards`'s; this file adds the arithmetic round
them, so that a cell whose objects are a few stripes long holds the
program to numbers and not to ranges:

  stripes       ceil(n / (k * chunk)), and one for an empty object
  padded        stripes * k * chunk: what is encoded, zeros past n
  shard         stripes * chunk: what each of the k+m OSDs keeps
  at rest       (k + m) * shard
  up the link   padded: the data chunks go to the device once
  down the link m * shard: the parity chunks come back once

At n = 65536 on k=8 m=3 chunk=4096: two stripes, nothing padded, eleven
shards of 8 KiB, 90,112 bytes at rest and as many over the link, both
1.375 bytes a user byte, (k + m) / k exactly.
"""
from __future__ import annotations

import numpy as np

from benchmarks import reference


def layout(nbytes: int, k: int, m: int, chunk: int) -> dict:
    """How a whole-object write of `nbytes` is striped."""
    width = k * chunk
    stripes = max(1, -(-nbytes // width))
    return {"stripes": stripes, "padded_bytes": stripes * width,
            "shard_bytes": stripes * chunk, "shards": k + m}


def shards(value: bytes, k: int, m: int, chunk: int) -> np.ndarray:
    """(k+m, shard_bytes) uint8: each shard's bytes for an object whose
    content is `value`."""
    out = reference.expected_shards(value, k, m, chunk)
    assert out.shape == (k + m, layout(len(value), k, m, chunk)["shard_bytes"])
    return out


def least_bytes(nbytes: int, k: int, m: int, chunk: int) -> dict:
    """The least one such write moves: to the stores, and each way over
    the host-device link when the parity is computed on the device."""
    lay = layout(nbytes, k, m, chunk)
    return {"at_rest": (k + m) * lay["shard_bytes"],
            "link_up": lay["padded_bytes"],
            "link_down": m * lay["shard_bytes"]}


def store_bytes_per_user_byte(nbytes: int, k: int, m: int, chunk: int) -> float:
    return least_bytes(nbytes, k, m, chunk)["at_rest"] / nbytes


def link_bytes_per_user_byte(nbytes: int, k: int, m: int, chunk: int) -> float:
    least = least_bytes(nbytes, k, m, chunk)
    return (least["link_up"] + least["link_down"]) / nbytes
