"""Traffic of upstream's `rados bench` (src/common/obj_bencher.cc):
`write` creates a new object of the configured size with every op,
`seq` reads objects written beforehand in the order they were written.
The shape copies `ceph_tpu/tools/rados_bench._worker`; the payloads do
not: a pool of distinct buffers is drawn from the seed in set-up, so
nothing is generated on the event loop inside the window and a read is
checked with one `bytes ==`.

The seed decides the payload bytes and which buffer each object gets;
names, sizes and the order of ops are the same for every seed.
"""
from __future__ import annotations

import numpy as np

KINDS = ("write", "seq")


class Generator:
    def __init__(self, config: dict, traffic: dict, seed: int):
        if traffic["op"] not in KINDS:
            raise ValueError(f"radosbench: op {traffic['op']!r} is not one "
                             f"of {KINDS}")
        self.op = traffic["op"]
        self.clients = int(traffic["clients"])
        self.warmup_ops = int(traffic["warmup_ops"])
        self.preload_objects = int(traffic.get("preload_objects", 0))
        if self.op == "seq" and self.preload_objects <= 0:
            raise ValueError("radosbench: seq needs preload_objects")
        self.object_size = int(config["object_size"])
        self.object_bytes = self.object_size
        rng = np.random.default_rng([seed, 1])
        n_pool = int(traffic["payload_pool"])
        self._pool = [rng.bytes(self.object_size) for _ in range(n_pool)]
        self._order = rng.permutation(n_pool)
        self._issued = 0

    @staticmethod
    def _name(i: int) -> str:
        return f"benchmark_data_obj{i:08d}"

    def preload(self) -> list[tuple]:
        return [("write", self._name(i), 0)
                for i in range(self.preload_objects)]

    def next_op(self) -> tuple:
        i = self._issued
        self._issued += 1
        if self.op == "write":
            return ("write", self._name(self.preload_objects + i), 0)
        return ("read", self._name(i % self.preload_objects), None)

    def value_of(self, name: str, version: int) -> bytes:
        i = int(name[-8:])
        return self._pool[self._order[i % len(self._order)]]


def make(config: dict, traffic: dict, seed: int) -> Generator:
    return Generator(config, traffic, seed)
