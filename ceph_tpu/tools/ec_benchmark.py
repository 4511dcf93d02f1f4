"""Erasure-code benchmark — re-creation of `ceph_erasure_code_benchmark`.

Mirrors the reference tool's CLI and semantics
(src/test/erasure-code/ceph_erasure_code_benchmark.cc:49-87 options,
:165-193 encode loop, :254-324 decode with random/exhaustive erasures) and
its output format: one line `seconds \t KiB_processed` so `bench.sh`-style
drivers compute GB/s = KiB / 2^20 / seconds
(qa/workunits/erasure-code/bench.sh:214).

TPU-specific extensions (absent in the reference because CPU plugins have no
dispatch latency to amortize):

  --mode scalar    per-stripe encode() via the plugin contract (reference
                   semantics, one device round trip per stripe)
  --mode batched   many stripes per device dispatch through
                   encode_stripes/decode_stripes (the ECUtil batching site)
  --mode baseline  numpy host codec (mat_vec_apply ground truth)
  --mode native    C++ host codec from native/ (split-table SIMD, the
                   stand-in for the reference isa plugin's CPU kernels)
  --batch N        stripes per dispatch for --mode batched
  --warmup N       untimed iterations first (XLA compiles each new shape
                   on its first call; the reference has no JIT so needs
                   no warmup)

Programmatic use: `run_bench(BenchConfig(...)) -> BenchResult`.
"""
from __future__ import annotations

import argparse
import dataclasses
import random
import sys
import time
from typing import Iterable

import numpy as np


@dataclasses.dataclass
class BenchConfig:
    plugin: str = "jerasure"
    workload: str = "encode"          # encode | decode
    size: int = 1024 * 1024           # bytes per in-buffer (stripe)
    iterations: int = 1
    erasures: int = 1
    erased: tuple[int, ...] = ()      # explicit erased chunk ids
    erasures_generation: str = "random"  # random | exhaustive
    parameters: dict = dataclasses.field(default_factory=dict)
    mode: str = "scalar"              # scalar | batched | baseline | native
    batch: int = 32
    warmup: int = 1
    verbose: bool = False
    seed: int | None = None


@dataclasses.dataclass
class BenchResult:
    seconds: float
    kib: float                        # KiB processed (reference accounting)
    config: BenchConfig

    @property
    def gb_per_s(self) -> float:
        # bench.sh:214 accounting: GB/s = KiB / 2^20 / seconds
        return self.kib / (1 << 20) / self.seconds if self.seconds > 0 else 0.0


def _make_instance(cfg: BenchConfig):
    from ceph_tpu.ec.registry import ErasureCodePluginRegistry

    profile = dict(cfg.parameters)
    profile.setdefault("plugin", cfg.plugin)
    return ErasureCodePluginRegistry.instance().factory(cfg.plugin, profile)


def _erasure_patterns(cfg: BenchConfig, n_chunks: int,
                      rng: random.Random) -> Iterable[tuple[int, ...]]:
    """Patterns of chunk ids to erase for one decode iteration."""
    if not cfg.erased and cfg.erasures > n_chunks:
        raise ValueError(
            f"--erasures {cfg.erasures} exceeds chunk count {n_chunks}")
    if cfg.erased:
        yield tuple(cfg.erased)
    elif cfg.erasures_generation == "exhaustive":
        import itertools
        yield from itertools.combinations(range(n_chunks), cfg.erasures)
    else:
        chosen: set[int] = set()
        while len(chosen) < cfg.erasures:
            chosen.add(rng.randrange(n_chunks))
        yield tuple(sorted(chosen))


# ---------------------------------------------------------------------------
# Scalar (plugin-contract) workloads — reference semantics
# ---------------------------------------------------------------------------

def _time_host_loop(fn, iterations: int, warmup: int) -> float:
    """Time `iterations` synchronous calls of fn() after `warmup` untimed
    ones (shared by every host-side bench path)."""
    for _ in range(warmup):
        fn()
    t0 = time.perf_counter()
    for _ in range(iterations):
        fn()
    return max(time.perf_counter() - t0, 1e-9)


def _bench_encode_scalar(cfg: BenchConfig, code) -> BenchResult:
    data = b"X" * cfg.size
    want = set(range(code.get_chunk_count()))
    dt = _time_host_loop(lambda: code.encode(want, data),
                         cfg.iterations, cfg.warmup)
    return BenchResult(dt, cfg.iterations * (cfg.size / 1024), cfg)


def _bench_decode_scalar(cfg: BenchConfig, code) -> BenchResult:
    data = b"X" * cfg.size
    n = code.get_chunk_count()
    encoded = code.encode(set(range(n)), data)
    chunk_size = len(encoded[0])
    rng = random.Random(cfg.seed)
    want = set(range(n))

    def one_pass():
        for pattern in _erasure_patterns(cfg, n, rng):
            chunks = {i: b for i, b in encoded.items() if i not in pattern}
            decoded = code.decode(want, chunks, chunk_size)
            for i in pattern:
                if decoded[i] != encoded[i]:
                    raise RuntimeError(f"chunk {i} decode mismatch")

    dt = _time_host_loop(one_pass, cfg.iterations, cfg.warmup)
    return BenchResult(dt, cfg.iterations * (cfg.size / 1024), cfg)


# ---------------------------------------------------------------------------
# Batched workloads — the TPU amortization path (ECUtil batching site)
# ---------------------------------------------------------------------------

def _time_device_loop(fn, iterations: int, warmup: int) -> float:
    """Time `iterations` calls of fn() (device dispatches). The device
    runs enqueued programs in order, so blocking on the last result
    covers the whole loop."""
    import jax

    out = fn()
    for _ in range(max(0, warmup - 1)):
        out = fn()
    jax.block_until_ready(out)     # warm: compile + drain queue
    t0 = time.perf_counter()
    for _ in range(iterations):
        out = fn()
    jax.block_until_ready(out)
    return max(time.perf_counter() - t0, 1e-9)


def _device_test_data(batch: int, k: int, chunk: int):
    """Pseudo-random uint8 stripes generated ON DEVICE: the device-resident
    benches time the kernel, so their working set never crosses the link."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def gen():
        i = jnp.arange(batch * k * chunk, dtype=jnp.uint32)
        return ((i * jnp.uint32(2654435761)) >> 7).astype(jnp.uint8).reshape(
            batch, k, chunk)

    return gen()


def _bench_encode_batched(cfg: BenchConfig, code) -> BenchResult:
    k = code.get_data_chunk_count()
    chunk = code.get_chunk_size(cfg.size)
    dev = _device_test_data(cfg.batch, k, chunk)
    dt = _time_device_loop(lambda: code.encode_stripes(dev),
                           cfg.iterations, cfg.warmup)
    return BenchResult(dt, cfg.iterations * cfg.batch * (cfg.size / 1024), cfg)


def _bench_encode_batched_host(cfg: BenchConfig, code) -> BenchResult:
    """Batched, but with host-resident numpy buffers: includes the H2D/D2H
    transfers the OSD bridge pays, pipelined by the plugin."""
    k = code.get_data_chunk_count()
    chunk = code.get_chunk_size(cfg.size)
    data = np.full((cfg.batch, k, chunk), ord("X"), dtype=np.uint8)
    dt = _time_host_loop(lambda: code.encode_stripes(data),
                         cfg.iterations, cfg.warmup)
    return BenchResult(dt, cfg.iterations * cfg.batch * (cfg.size / 1024), cfg)


def _bench_decode_batched(cfg: BenchConfig, code) -> BenchResult:
    k = code.get_data_chunk_count()
    n = code.get_chunk_count()
    chunk = code.get_chunk_size(cfg.size)
    rng = random.Random(cfg.seed)
    pattern = next(iter(_erasure_patterns(cfg, n, rng)))
    avail = tuple(i for i in range(n) if i not in pattern)[:k]
    want = tuple(pattern)
    dev = _device_test_data(cfg.batch, k, chunk)
    dt = _time_device_loop(lambda: code.decode_stripes(avail, want, dev),
                           cfg.iterations, cfg.warmup)
    return BenchResult(dt, cfg.iterations * cfg.batch * (cfg.size / 1024), cfg)


# ---------------------------------------------------------------------------
# Host-CPU baselines
# ---------------------------------------------------------------------------

def _baseline_matrix(cfg: BenchConfig, code):
    M = getattr(code, "coding_matrix", None)
    if M is None:
        raise RuntimeError(f"plugin {cfg.plugin} exposes no coding matrix")
    return np.asarray(M, dtype=np.uint8)


def _bench_encode_baseline(cfg: BenchConfig, code) -> BenchResult:
    """numpy ground-truth codec on host CPU."""
    from ceph_tpu.ec import gf256

    M = _baseline_matrix(cfg, code)
    k = code.get_data_chunk_count()
    chunk = code.get_chunk_size(cfg.size)
    data = np.full((k, chunk), ord("X"), dtype=np.uint8)
    dt = _time_host_loop(lambda: gf256.mat_vec_apply(M, data),
                         cfg.iterations, cfg.warmup)
    return BenchResult(dt, cfg.iterations * (cfg.size / 1024), cfg)


def _bench_encode_native(cfg: BenchConfig, code) -> BenchResult:
    """C++ split-table codec from native/ — the isa-plugin stand-in."""
    from ceph_tpu.native import ec_native

    M = _baseline_matrix(cfg, code)
    k = code.get_data_chunk_count()
    chunk = code.get_chunk_size(cfg.size)
    data = np.full((k, chunk), ord("X"), dtype=np.uint8)
    out = np.zeros((M.shape[0], chunk), dtype=np.uint8)
    dt = _time_host_loop(lambda: ec_native.encode(M, data, out),
                         cfg.iterations, cfg.warmup)
    return BenchResult(dt, cfg.iterations * (cfg.size / 1024), cfg)


def _bench_decode_baseline(cfg: BenchConfig, code, native: bool) -> BenchResult:
    from ceph_tpu.ec import gf256
    from ceph_tpu.ops import rs_codec

    M = _baseline_matrix(cfg, code)
    k = code.get_data_chunk_count()
    n = code.get_chunk_count()
    chunk = code.get_chunk_size(cfg.size)
    rng = random.Random(cfg.seed)
    pattern = next(iter(_erasure_patterns(cfg, n, rng)))
    avail = tuple(i for i in range(n) if i not in pattern)[:k]
    R = rs_codec.recovery_matrix(M, avail, tuple(pattern))
    data = np.full((k, chunk), ord("X"), dtype=np.uint8)
    if native:
        from ceph_tpu.native import ec_native
        out = np.zeros((R.shape[0], chunk), dtype=np.uint8)
        fn = lambda: ec_native.encode(R, data, out)
    else:
        fn = lambda: gf256.mat_vec_apply(R, data)
    dt = _time_host_loop(fn, cfg.iterations, cfg.warmup)
    return BenchResult(dt, cfg.iterations * (cfg.size / 1024), cfg)


# ---------------------------------------------------------------------------
# Entry points
# ---------------------------------------------------------------------------

def run_bench(cfg: BenchConfig) -> BenchResult:
    code = _make_instance(cfg)
    if cfg.workload == "encode":
        if cfg.mode == "scalar":
            return _bench_encode_scalar(cfg, code)
        if cfg.mode == "batched":
            return _bench_encode_batched(cfg, code)
        if cfg.mode == "batched-host":
            return _bench_encode_batched_host(cfg, code)
        if cfg.mode == "baseline":
            return _bench_encode_baseline(cfg, code)
        if cfg.mode == "native":
            return _bench_encode_native(cfg, code)
    elif cfg.workload == "decode":
        if cfg.mode == "scalar":
            return _bench_decode_scalar(cfg, code)
        if cfg.mode == "batched":
            return _bench_decode_batched(cfg, code)
        if cfg.mode == "baseline":
            return _bench_decode_baseline(cfg, code, native=False)
        if cfg.mode == "native":
            return _bench_decode_baseline(cfg, code, native=True)
    raise ValueError(f"unknown workload/mode {cfg.workload}/{cfg.mode}")


def parse_args(argv: list[str] | None = None) -> BenchConfig:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("-s", "--size", type=int, default=1024 * 1024)
    p.add_argument("-i", "--iterations", type=int, default=1)
    p.add_argument("-p", "--plugin", default="jerasure")
    p.add_argument("-w", "--workload", default="encode",
                   choices=["encode", "decode"])
    p.add_argument("-e", "--erasures", type=int, default=1)
    p.add_argument("--erased", type=int, action="append", default=[])
    p.add_argument("-E", "--erasures-generation", default="random",
                   choices=["random", "exhaustive"])
    p.add_argument("-P", "--parameter", action="append", default=[])
    p.add_argument("-v", "--verbose", action="store_true")
    p.add_argument("--mode", default="scalar",
                   choices=["scalar", "batched", "batched-host",
                            "baseline", "native"])
    p.add_argument("--batch", type=int, default=32)
    p.add_argument("--warmup", type=int, default=1)
    p.add_argument("--seed", type=int, default=None)
    a = p.parse_args(argv)
    params = {}
    for kv in a.parameter:
        if kv.count("=") != 1:
            print(f"--parameter {kv} ignored because it does not contain "
                  "exactly one =", file=sys.stderr)
            continue
        key, val = kv.split("=")
        params[key] = val
    return BenchConfig(
        plugin=a.plugin, workload=a.workload, size=a.size,
        iterations=a.iterations, erasures=a.erasures,
        erased=tuple(a.erased), erasures_generation=a.erasures_generation,
        parameters=params, mode=a.mode, batch=a.batch, warmup=a.warmup,
        verbose=a.verbose, seed=a.seed)


def main(argv: list[str] | None = None) -> int:
    cfg = parse_args(argv)
    res = run_bench(cfg)
    # reference output format: seconds \t KiB (ceph_erasure_code_benchmark.cc:193)
    print(f"{res.seconds:.6f}\t{res.kib:.0f}")
    if cfg.verbose:
        print(f"# {res.gb_per_s:.3f} GB/s mode={cfg.mode} plugin={cfg.plugin}",
              file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
