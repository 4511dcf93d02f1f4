"""The benchmark's one entry:

    python -m benchmarks.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process: it loads, warms up, measures for `--seconds`, prints one
JSON object as the last line of its standard output and exits. It runs
on the TPU only and never falls back to the CPU; the tests drive
`benchmarks.harness` on the CPU backend through functions.

`--out` (where the per-second series and the trace go; default
`.bench_out/` in the checkout) and `--control` (break a guarantee on
purpose and see `correct` come out false; see benchmarks/README.md) are
for the builder's own runs. The driver passes neither.
"""
from __future__ import annotations

import argparse
import asyncio
import json
import os
import shutil
import sys
import time

_T0 = "BENCH_START_MONOTONIC"


def _pin_process(argv: list[str]) -> float:
    """What the harness owns about the process: the hash seed. Python
    fixes it at start-up, so the entry re-executes itself once with
    PYTHONHASHSEED=0 (the data seed is --seed, not this). Returns the
    first process's start on the monotonic clock, which the boot shares."""
    if os.environ.get("PYTHONHASHSEED") == "0" and _T0 in os.environ:
        return float(os.environ[_T0])
    env = dict(os.environ, PYTHONHASHSEED="0")
    env.setdefault(_T0, repr(time.monotonic()))
    sys.stdout.flush()
    os.execve(sys.executable,
              [sys.executable, "-m", "benchmarks.run", *argv], env)


def select_device(chips: int):
    """Import jax with the TPU as the only acceptable backend (as
    chip_smoke.py does) and return its devices."""
    want = os.environ.get("JAX_PLATFORMS")
    if want is None:
        os.environ["JAX_PLATFORMS"] = "tpu"
    elif want.split(",")[0].strip() != "tpu":
        raise SystemExit(f"benchmark: JAX_PLATFORMS={want!r} does not put "
                         f"the TPU first; the benchmark runs on the chip "
                         f"only")
    import ceph_tpu  # noqa: F401  places the compile cache in the checkout
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(f"benchmark: jax came up on platform "
                         f"{devices[0].platform!r}, not 'tpu'")
    if len(devices) < chips:
        raise SystemExit(f"benchmark: the cell asks for {chips} chip(s), "
                         f"jax reports {len(devices)}")
    # every program goes into the persistent cache, however quickly it
    # compiled, so that only a checkout's first run compiles
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return devices


def main(argv: list[str] | None = None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    ap.add_argument("--out", default=None)
    ap.add_argument("--control", default="")
    args = ap.parse_args(argv)
    t_start = _pin_process(argv)

    from benchmarks import harness

    cell = harness.load_cell(args.workload)
    select_device(cell.chips)
    out_dir = os.path.join(
        args.out or os.path.join(harness.ROOT, ".bench_out"),
        f"{args.workload}-seed{args.seed}-trace{args.trace}")
    shutil.rmtree(out_dir, ignore_errors=True)
    control = tuple(c for c in args.control.split(",") if c)
    done = asyncio.run(harness.run_cell(
        cell, args.seed, args.seconds, bool(args.trace), out_dir, t_start,
        control))
    print(f"benchmark: {json.dumps(done['info'])}")
    # each number compared beside its limit: the last lines of standard
    # error, and the last key of the result's line
    for name, value, limit in done["checks"]:
        print(f"benchmark: check {name} = {value} (limit {limit})",
              file=sys.stderr)
    sys.stderr.flush()
    checks = {name: {"value": value, "limit": limit}
              for name, value, limit in done["checks"]}
    print(json.dumps({**done["result"], "checks": checks}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
