"""YCSB's core workload (github.com/brianfrankcooper/YCSB,
core/src/main/java/site/ycsb/workloads/CoreWorkload.java) through the
shape of its `rados` binding: one object per record, a read fetches the
whole object, an update is a `write_full` of a whole new record.

Keys are drawn zipfian with YCSB's constant. The draw copies
`ceph_tpu/tools/rados_swarm._ZipfPicker` (cumulative weights, one
bisect per draw), vectorised; the ranks are scrambled over the key
space in the order of YCSB's 64-bit FNV hash of the key number, and a
record's name is "user" + that hash, as `CoreWorkload.buildKeyName`
makes it.

The seed decides the record bytes and the order of op types and key
draws; the key space, names and sizes are the same for every seed, and
so is the work: ops come in blocks of 4096, and every block of every
seed holds the same multiset of keys (each rank as often as the
zipfian gives it, by largest remainder) and the same count of reads,
shuffled by the seed. Independent draws would give one seed 51% updates
and a hotter first key and another 49% and a cooler one; two runs of
one code would then differ by their seeds. A
record's value is a slice of a seed-drawn blob behind a 16-byte stamp
of (key, version), so every version of every record is distinct and
costs one concatenation to make.
"""
from __future__ import annotations

import struct

import numpy as np

_FNV_OFFSET = 0xCBF29CE484222325
_FNV_PRIME = 0x100000001B3
_BLOCK = 1 << 12        # ops in a block: about one window of the slower mix


def fnvhash64(val: int) -> int:
    """site.ycsb.Utils.fnvhash64: FNV-1 over the 8 octets of `val`,
    lowest first, made non-negative the way Java's Math.abs does."""
    h = _FNV_OFFSET
    for _ in range(8):
        octet = val & 0xFF
        val >>= 8
        h ^= octet
        h = (h * _FNV_PRIME) & 0xFFFFFFFFFFFFFFFF
    if h >= 1 << 63:                 # a negative Java long
        h = (1 << 64) - h
    return h & 0x7FFFFFFFFFFFFFFF


class Generator:
    def __init__(self, config: dict, traffic: dict, seed: int):
        self.clients = int(traffic["clients"])
        self.warmup_ops = int(traffic["warmup_ops"])
        self.records = int(config["recordcount"])
        self.record_size = int(config["fieldcount"]) * \
            int(config["fieldlength"])
        self.object_bytes = self.record_size
        self.read_share = float(traffic["readproportion"])
        if abs(self.read_share + float(traffic["updateproportion"]) - 1) \
                > 1e-9:
            raise ValueError("ycsb: readproportion + updateproportion "
                             "must be 1 (no other op is implemented)")
        if traffic["requestdistribution"] != "zipfian":
            raise ValueError("ycsb: only requestdistribution=zipfian")
        hashes = [fnvhash64(i) for i in range(self.records)]
        self.names = [f"user{h}" for h in hashes]
        self._key_of_name = {n: i for i, n in enumerate(self.names)}
        # rank r (0 = hottest) -> the key with the r-th smallest hash
        self._key_of_rank = np.argsort(np.asarray(hashes, dtype=np.uint64),
                                       kind="stable")
        weights = 1.0 / np.arange(1, self.records + 1) ** \
            float(config["zipfian_constant"])
        share = weights / weights.sum() * _BLOCK
        count = np.floor(share).astype(np.int64)
        short = _BLOCK - int(count.sum())
        count[np.argsort(-(share - count), kind="stable")[:short]] += 1
        self._block_ranks = np.repeat(np.arange(self.records), count)
        self._block_reads = np.arange(_BLOCK) < round(_BLOCK
                                                       * self.read_share)
        self._rng = np.random.default_rng([seed, 2])
        self._blob = np.random.default_rng([seed, 3]).bytes(1 << 20)
        self._versions = [0] * self.records
        self._kinds: list[bool] = []
        self._keys: list[int] = []
        self._at = 0

    def _draw(self) -> None:
        """The next block of (is_read, key) pairs: the fixed multisets,
        in the order the seed's stream gives them."""
        ranks = self._rng.permutation(self._block_ranks)
        self._keys = self._key_of_rank[ranks].tolist()
        self._kinds = self._rng.permutation(self._block_reads).tolist()
        self._at = 0

    def draw_ranks(self, n: int) -> np.ndarray:
        """The ranks of n ops' keys, block by block (for the tests)."""
        blocks = [self._rng.permutation(self._block_ranks)
                  for _ in range(-(-n // _BLOCK))]
        return np.concatenate(blocks)[:n]

    def preload(self) -> list[tuple]:
        return [("write", name, 0) for name in self.names]

    def next_op(self) -> tuple:
        if self._at >= len(self._keys):
            self._draw()
        key, is_read = self._keys[self._at], self._kinds[self._at]
        self._at += 1
        if is_read:
            return ("read", self.names[key], None)
        self._versions[key] += 1
        return ("write", self.names[key], self._versions[key])

    def value_of(self, name: str, version: int) -> bytes:
        key = self._key_of_name[name]
        off = (key * 2654435761 + version * 40503) % \
            (len(self._blob) - self.record_size)
        return struct.pack("<QQ", key, version) + \
            self._blob[off + 16: off + self.record_size]


def make(config: dict, traffic: dict, seed: int) -> Generator:
    return Generator(config, traffic, seed)
