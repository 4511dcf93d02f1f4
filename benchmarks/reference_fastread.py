"""The plain reference for a fast read of an erasure pool: which of the
shard replies a primary must answer from, and what the answer is.

It imports nothing of the program: the decoding is
`benchmarks.reference_decode`'s Gauss-Jordan elimination over GF(2^8)
on `benchmarks.reference`'s field and coding matrix. Upstream's words
(doc/rados/operations/pools.rst, `fast_read`): "the read request would
issue sub reads to all shards, and waits until it receives enough shards
to decode to serve the client ... once the first K replies return,
client's request is served immediately using the data decoded from these
replies".

A reply is `(shard, version, bytes)`: the shard's position 0..k+m-1, the
version of the write that produced the chunk (anything ordered; chunks
of two writes never combine), and the shard's bytes, whole stripes of
`chunk` bytes each. The primary's own chunk is the first reply.
"""
from __future__ import annotations

import numpy as np

from benchmarks.reference_decode import reconstruct


def _first_k(arrivals, k: int):
    """Walk the replies in the order they came until one version holds
    k chunks: (that version, its {shard: bytes}, every version seen till
    then, the replies looked at). None where the replies run out
    first."""
    by_version: dict = {}
    for taken, (shard, version, data) in enumerate(arrivals, 1):
        chunks = by_version.setdefault(version, {})
        chunks[shard] = data
        if len(chunks) == k:
            return version, chunks, set(by_version), taken
    return None


def may_answer(arrivals, k: int) -> bool:
    """False where no version reaches k chunks, and where a newer
    version than the one that does was seen on the way: answering from
    the older one could take back a write that was acknowledged, so the
    read fails (EIO) instead."""
    found = _first_k(arrivals, k)
    if found is None:
        return False
    version, _chunks, seen, _taken = found
    return max(seen) == version


def answer(arrivals, k: int, m: int, chunk: int) -> dict:
    """What a fast read makes of `arrivals`: `used`, the k positions it
    answers from (sorted); `want`, the data positions among them that
    are missing and have to be rebuilt; `r`, how many those are; `late`,
    the replies it did not wait for; and `data`, the object's padded
    bytes (whole stripes; the caller cuts them to the object's size)."""
    found = _first_k(arrivals, k)
    if found is None:
        raise ValueError(f"fast read: no version has {k} chunks among "
                         f"{[(s, v) for s, v, _ in arrivals]}")
    _version, chunks, _seen, taken = found
    used = sorted(chunks)
    want = [j for j in range(k) if j not in chunks]
    rows = reconstruct({j: np.frombuffer(chunks[j], dtype=np.uint8)
                        for j in used}, k, m)               # (k, n)
    stripes = rows.shape[1] // chunk
    # shard j holds stripe s's chunk at s*chunk: the object is the data
    # shards' chunks, stripe by stripe
    data = rows.reshape(k, stripes, chunk).transpose(1, 0, 2).tobytes()
    return {"used": used, "want": want, "r": len(want),
            "late": len(arrivals) - taken, "data": data}


def expected_decode_share(q: float, k: int,
                          remote_data: int | None = None) -> float:
    """The share of fast reads that reconstruct when every shard's reply
    is held back with probability q, independently, and an unheld reply
    of a data position always beats a parity's: a read decodes when any
    of the data positions it asks over the wire is held. The primary
    holds one data position itself, so `remote_data` is k - 1."""
    if remote_data is None:
        remote_data = k - 1
    return 1.0 - (1.0 - q) ** remote_data
