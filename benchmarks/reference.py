"""The plain reference the benchmark holds the object store to.

It imports nothing of the program and takes nothing the program made:
the object model is a dictionary, the erasure code is Reed-Solomon over
GF(2^8) done with shifts and xors on numpy arrays (no tables shared
with `ceph_tpu.ec.gf256`), and the coding matrix is built here by the
published jerasure `reed_sol_van` construction.

Two things are compared against it:

* every read the window makes (`ObjectModel.begin_read`/`end_read`): the bytes a
  read returns must be a value the object could hold between the read's
  start and its end — the last acknowledged write, or a write that was
  in flight meanwhile;
* the k+m shard blobs the OSD stores hold for sampled objects
  (`expected_shards`): data shards are the value striped `chunk` bytes
  at a time over k shards, zero padded to whole stripes; parity shards
  are the coding matrix applied to them, byte for byte.
"""
from __future__ import annotations

import numpy as np

_POLY = 0x11D        # x^8+x^4+x^3+x^2+1, the field jerasure uses at w=8


def _xtime(a: np.ndarray) -> np.ndarray:
    """a * x in GF(2^8), elementwise on uint8."""
    wide = a.astype(np.uint16) << 1
    return (wide ^ np.where(wide & 0x100, _POLY, 0)).astype(np.uint8)


def gf_mul(c: int, data: np.ndarray) -> np.ndarray:
    """The constant c times every byte of `data`, by shift and add."""
    out = np.zeros_like(data)
    term = data
    while c:
        if c & 1:
            out = out ^ term
        term = _xtime(term)
        c >>= 1
    return out


def _mul(a: int, b: int) -> int:
    return int(gf_mul(a, np.array([b], dtype=np.uint8))[0])


def _inv(a: int) -> int:
    """a^254, which is a^-1 in a field of 256 elements."""
    out, sq = 1, a
    for bit in range(1, 8):
        sq = _mul(sq, sq)
        out = _mul(out, sq)
    return out


def reed_sol_van_matrix(k: int, m: int) -> np.ndarray:
    """jerasure's systematic Vandermonde coding matrix (m, k): the
    extended Vandermonde matrix, its top k rows reduced to the identity
    by column operations, the coding rows then scaled so that the first
    of them is all ones and every later one starts with 1 (Plank,
    "Note: Correction to the 1997 tutorial on Reed-Solomon coding")."""
    rows = k + m
    v = np.zeros((rows, k), dtype=np.uint8)
    v[0, 0] = 1
    v[rows - 1, k - 1] = 1
    for i in range(1, rows - 1):
        x = 1
        for j in range(k):
            v[i, j] = x
            x = _mul(x, i)
    for i in range(1, k):
        j = next(r for r in range(i, rows) if v[r, i])
        if j != i:
            v[[i, j]] = v[[j, i]]
        if v[i, i] != 1:
            v[:, i] = gf_mul(_inv(int(v[i, i])), v[:, i])
        for c in range(k):
            if c != i and v[i, c]:
                v[:, c] ^= gf_mul(int(v[i, c]), v[:, i])
    coding = v[k:].copy()
    for j in range(k):
        if coding[0, j] not in (0, 1):
            coding[:, j] = gf_mul(_inv(int(coding[0, j])), coding[:, j])
    for i in range(1, m):
        if coding[i, 0] not in (0, 1):
            coding[i] = gf_mul(_inv(int(coding[i, 0])), coding[i])
    return coding


def expected_shards(value: bytes, k: int, m: int, chunk: int) -> np.ndarray:
    """(k+m, stripes*chunk) uint8: what each shard's OSD must hold for
    an object whose content is `value`."""
    width = k * chunk
    stripes = max(1, -(-len(value) // width))
    buf = np.zeros(stripes * width, dtype=np.uint8)
    buf[:len(value)] = np.frombuffer(value, dtype=np.uint8)
    # stripe s, shard j, byte b  ->  shard j holds chunk s at s*chunk
    data = buf.reshape(stripes, k, chunk).transpose(1, 0, 2) \
        .reshape(k, stripes * chunk)
    coding = reed_sol_van_matrix(k, m)
    parity = np.zeros((m, data.shape[1]), dtype=np.uint8)
    for i in range(m):
        for j in range(k):
            parity[i] ^= gf_mul(int(coding[i, j]), data[j])
    return np.concatenate([data, parity])


class ObjectModel:
    """What each object may hold, kept as version numbers: the caller
    turns a version into bytes (`value_of(name, version)`), so nothing
    large lives here.

    A write is a candidate from the moment it is sent. When a write is
    acknowledged, every write that had *ended before it began* can no
    longer be the object's value; writes that overlapped it stay
    candidates, because either order is a legal outcome; so does a
    write that failed, whose outcome nobody knows. A read may return any
    candidate at its start or any write sent while it ran."""

    def __init__(self):
        self._seq = 0
        self._cand: dict[str, dict[int, list]] = {}   # name -> ver -> [begin, end]
        self._reads: dict[str, list[set]] = {}

    def _tick(self) -> int:
        self._seq += 1
        return self._seq

    def seed(self, name: str, version: int) -> None:
        """An object loaded before any concurrency: one candidate."""
        self._cand[name] = {version: [self._tick(), self._tick()]}

    def begin_write(self, name: str, version: int) -> None:
        self._cand.setdefault(name, {})[version] = [self._tick(), None]
        for snap in self._reads.get(name, ()):
            snap.add(version)

    def ack_write(self, name: str, version: int) -> None:
        cand = self._cand[name]
        began = cand[version][0]
        cand[version][1] = self._tick()
        for v in [v for v, (_, end) in cand.items()
                  if end is not None and end < began]:
            del cand[v]

    def begin_read(self, name: str) -> set:
        snap = set(self._cand.get(name, ()))
        self._reads.setdefault(name, []).append(snap)
        return snap

    def end_read(self, name: str, snap: set) -> set:
        """Versions the finished read may have returned."""
        # by identity: two reads in flight may hold equal sets, and the
        # one that stays must be the one that goes on receiving writes
        reads = [r for r in self._reads[name] if r is not snap]
        if reads:
            self._reads[name] = reads
        else:
            del self._reads[name]
        return snap

    def candidates(self, name: str) -> set:
        return set(self._cand.get(name, ()))

    def names(self) -> list[str]:
        return sorted(self._cand)
