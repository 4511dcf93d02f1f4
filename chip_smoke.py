#!/usr/bin/env python3
"""The quickest proof that the system still starts, compiles and serves
on the chip: `python chip_smoke.py [--seed N]`, one process, TPU only.

Two phases, each checked against the plain reference, neither wrapped
in a try/except — any failure is a traceback and a non-zero exit:

  kernels  through the plugin registry (`plugin=tpu k=8 m=3`), at the
           north-star width (BASELINE.json): encode_stripes and a
           3-erasure decode_stripes on a device-resident (16, 8, 1 MiB)
           batch against `gf256.mat_vec_apply`, and the device crc32c
           over 65,536 blocks of 4 KiB against `ec_native.crc32c_blocks`.
  served   an 11-OSD cluster in this process, EC pool `plugin=tpu k=8
           m=3` with pg_num 32, offload service on: 256 objects of
           4 MiB written 16 in flight (the `rados bench` defaults), read
           back and compared; parity shards of sampled objects taken
           from the OSD stores and compared with the reference; then 3
           OSDs stopped and 32 objects read back degraded.

The phases are plain functions so the tests can run them small on the
CPU backend; only this script's entry refuses any platform but `tpu`.
The last line of stdout is the verdict; the line before it carries what
the run measured, printed without judging it.
"""
from __future__ import annotations

import argparse
import asyncio
import gc
import json
import logging
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

# places the compile cache, and fails at once in a directory that holds
# this script and nothing else of the repo
import ceph_tpu  # noqa: E402,F401

K, M = 8, 3
PROFILE = {"plugin": "tpu", "k": str(K), "m": str(M)}
BLOCK = 4096


def select_device() -> dict:
    """Import jax with the TPU as the only acceptable default backend
    and describe what came up. With JAX_PLATFORMS unset jax would fall
    back to the CPU when the chip is missing or busy, so it is set."""
    want = os.environ.get("JAX_PLATFORMS")
    if want is None:
        os.environ["JAX_PLATFORMS"] = "tpu"
    elif want.split(",")[0].strip() != "tpu":
        raise SystemExit(f"chip_smoke: JAX_PLATFORMS={want!r} does not "
                         f"put the TPU first; this script runs on the "
                         f"chip only")
    t0 = time.perf_counter()
    import jax
    import jaxlib
    devices = jax.devices()
    init_s = time.perf_counter() - t0
    if devices[0].platform != "tpu":
        raise SystemExit(f"chip_smoke: jax came up on platform "
                         f"{devices[0].platform!r}, not 'tpu'")
    try:
        import libtpu
        libtpu_version = libtpu.__version__
    except ImportError:
        libtpu_version = None
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": len(devices),
            "backend_init_s": round(init_s, 2),
            "jax": jax.__version__, "jaxlib": jaxlib.__version__,
            "libtpu": libtpu_version,
            "compile_cache_dir": jax.config.jax_compilation_cache_dir}


def _cache_entries(device: dict) -> int:
    """Programs in the persistent compile cache right now."""
    path = device["compile_cache_dir"]
    return len(os.listdir(path)) if os.path.isdir(path) else 0


def _first_and_warm(fn) -> tuple[object, float, float]:
    """(result, first-call seconds, warm-call seconds): the difference
    is what the first call spent tracing and compiling (or fetching the
    program from the persistent cache)."""
    import jax
    t0 = time.perf_counter()
    jax.block_until_ready(fn())
    first = time.perf_counter() - t0
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn())
    return out, first, time.perf_counter() - t0


def phase_kernels(seed: int, batch: int = 16, chunk: int = 1 << 20,
                  crc_blocks: int = 1 << 16) -> dict:
    """Encode, decode and crc at full width, each against its reference."""
    import jax
    import jax.numpy as jnp

    from ceph_tpu.ec import gf256
    from ceph_tpu.ec.registry import ErasureCodePluginRegistry
    from ceph_tpu.native import ec_native
    from ceph_tpu.ops import crc32c as crc_dev

    code = ErasureCodePluginRegistry.instance().factory("tpu", dict(PROFILE))
    rng = np.random.default_rng(seed)
    device = jax.devices()[0]
    out: dict = {}

    data = rng.integers(0, 256, (batch, K, chunk), dtype=np.uint8)
    dev = jax.device_put(data, device)
    parity, first, warm = _first_and_warm(lambda: code.encode_stripes(dev))
    ref_parity = np.stack([gf256.mat_vec_apply(code.coding_matrix, s)
                           for s in data])
    if not np.array_equal(np.asarray(parity), ref_parity):
        raise AssertionError("encode_stripes differs from "
                             "gf256.mat_vec_apply")
    out["encode"] = {"shape": list(data.shape),
                     "compile_s": round(first - warm, 3),
                     "warm_s": round(warm, 5)}

    # two data chunks and one parity chunk lost; the survivors are
    # gathered on the device from the data and the parity just made
    want = (1, 4, K + 1)
    avail = tuple(i for i in range(K + M) if i not in want)[:K]
    full = jnp.concatenate([dev, parity], axis=1)
    chunks = jax.block_until_ready(full[:, np.asarray(avail), :])
    rebuilt, first, warm = _first_and_warm(
        lambda: code.decode_stripes(avail, want, chunks))
    ref_full = np.concatenate([data, ref_parity], axis=1)
    if not np.array_equal(np.asarray(rebuilt), ref_full[:, list(want), :]):
        raise AssertionError("decode_stripes differs from the reference "
                             "chunks")
    out["decode"] = {"erased": list(want),
                     "compile_s": round(first - warm, 3),
                     "warm_s": round(warm, 5)}

    blocks = rng.integers(0, 256, (crc_blocks, BLOCK), dtype=np.uint8)
    dev_blocks = jax.device_put(blocks, device)
    crc_fn = crc_dev.get_device_crc(BLOCK)
    crcs, first, warm = _first_and_warm(lambda: crc_fn(dev_blocks))
    if not np.array_equal(np.asarray(crcs),
                          ec_native.crc32c_blocks(blocks.reshape(-1), BLOCK)):
        raise AssertionError("device crc32c differs from "
                             "ec_native.crc32c_blocks")
    out["crc32c"] = {"blocks": crc_blocks,
                     "compile_s": round(first - warm, 3),
                     "warm_s": round(warm, 5)}

    # the link, as the offload service uses it: one host staging buffer
    # reused across transfers
    buf = np.zeros(min(32 << 20, data.nbytes), dtype=np.uint8)
    jax.block_until_ready(jax.device_put(buf, device))
    times = []
    for _ in range(5):
        t0 = time.perf_counter()
        jax.block_until_ready(jax.device_put(buf, device))
        times.append(time.perf_counter() - t0)
    out["h2d_gb_s"] = round(buf.nbytes / sorted(times)[len(times) // 2] / 1e9,
                            3)
    stats = device.memory_stats()     # None on backends that keep none
    out["peak_bytes_in_use"] = stats["peak_bytes_in_use"] if stats else None
    return out


def _payload(seed: int, i: int, size: int) -> bytes:
    """Object i's bytes: aperiodic, so a lane or stripe mix-up cannot
    land on identical data."""
    return np.random.default_rng([seed, i]).bytes(size)


async def _run_ops(n_ops: int, in_flight: int, op) -> dict:
    """`op(i)` for i < n_ops, `in_flight` at a time; an op that raises
    fails the run. Returns MB/s-ready totals and latency percentiles."""
    nxt = iter(range(n_ops))
    lat: list[float] = []
    moved = 0

    async def worker() -> None:
        nonlocal moved
        for i in nxt:
            t0 = time.perf_counter()
            n = await op(i)
            lat.append(time.perf_counter() - t0)
            moved += n         # after the await: `+=` across it loses updates

    t0 = time.perf_counter()
    await asyncio.gather(*[worker() for _ in range(in_flight)])
    elapsed = time.perf_counter() - t0
    lat.sort()
    return {"ops": n_ops, "seconds": round(elapsed, 3),
            "mb_s": round(moved / elapsed / 1e6, 2),
            "p50_ms": round(lat[len(lat) // 2] * 1e3, 2),
            "p99_ms": round(lat[int(len(lat) * 0.99)] * 1e3, 2)}


def _shard_blobs(osds, pool: str, oid: str) -> dict[int, bytes]:
    """shard index -> the blob that shard's OSD holds for `oid`."""
    blobs: dict[int, bytes] = {}
    for osd in osds:
        for pg in osd.pgs.values():
            if pg.pool.name != pool:
                continue
            cid, gh = pg.backend.coll(), pg.backend.ghobject(oid)
            if osd.store.exists(cid, gh):
                blobs[pg.acting.index(osd.whoami)] = bytes(
                    osd.store.read(cid, gh))
    return blobs


def _markdowns_since(cursor: int) -> set[str]:
    """Entities the mon marked down since flight seq `cursor`. The
    recorder is a bounded ring that drops its oldest events first: a
    run that overflowed it may have lost a mark-down, and fails."""
    from ceph_tpu.utils import flight

    events = flight.events_since(cursor)["events"]
    if events and events[-1]["seq"] - cursor != len(events):
        raise AssertionError(
            f"flight ring overflowed: {events[-1]['seq'] - cursor} events "
            f"since the run began, {len(events)} kept")
    return {e["entity"] for e in events if e["type"] == "osd_markdown"}


async def phase_served(seed: int, n_objects: int = 256,
                       object_size: int = 4 << 20, in_flight: int = 16,
                       pg_num: int = 32, sampled: int = 8,
                       degraded_reads: int = 32) -> dict:
    """Write, read back, check parity at rest, then read degraded."""
    from ceph_tpu import offload
    from ceph_tpu.ec import gf256
    from ceph_tpu.msg import frames
    from ceph_tpu.tools.cluster_boot import ephemeral_cluster
    from ceph_tpu.utils import flight

    pool = "smoke"
    rng = np.random.default_rng(seed)
    cursor = flight.last_seq()
    async with ephemeral_cluster(K + M, prefix="chip-smoke-") \
            as (client, osds, _mon):
        await client.command({"prefix": "osd erasure-code-profile set",
                              "name": "smokeprof", "profile": dict(PROFILE)})
        await client.pool_create(pool, pg_num=pg_num, pool_type="erasure",
                                 erasure_code_profile="smokeprof")
        io = client.ioctx(pool)
        svc = offload.get_service()

        async def write(i: int) -> int:
            await io.write_full(f"obj{i}", _payload(seed, i, object_size))
            return object_size

        async def read(i: int) -> int:
            got = await io.read(f"obj{i}")
            if got != _payload(seed, i, object_size):
                raise AssertionError(f"obj{i} read back differs from what "
                                     f"was written")
            return object_size

        out = {"write": await _run_ops(n_objects, in_flight, write),
               "read": await _run_ops(n_objects, in_flight, read)}

        # a healthy read only concatenates data shards, so the parity the
        # device wrote is checked where it rests
        coding = gf256.reed_sol_van_matrix(K, M)
        for i in rng.choice(n_objects, size=min(sampled, n_objects),
                            replace=False):
            blobs = _shard_blobs(osds, pool, f"obj{i}")
            if sorted(blobs) != list(range(K + M)):
                raise AssertionError(f"obj{i}: shards {sorted(blobs)} found, "
                                     f"{K + M} expected")
            shards = np.stack([np.frombuffer(blobs[s], dtype=np.uint8)
                               for s in range(K + M)])
            if not np.array_equal(shards[K:],
                                  gf256.mat_vec_apply(coding, shards[:K])):
                raise AssertionError(f"obj{i}: parity shards at rest differ "
                                     f"from gf256.mat_vec_apply")

        wrong = _markdowns_since(cursor)
        if wrong:
            raise AssertionError(f"OSDs marked down under load: "
                                 f"{sorted(wrong)}")

        dead = [int(x) for x in rng.choice(K + M, size=M, replace=False)]
        for i in dead:
            await osds[i].stop()
        alive = [o for o in osds if o.whoami not in dead]
        deadline = time.monotonic() + 60
        while not all(i in m.osds and not m.osds[i].up
                      for m in [o.osdmap for o in alive] + [client.osdmap]
                      for i in dead):
            if time.monotonic() > deadline:
                raise TimeoutError(f"osds {dead} never marked down")
            await asyncio.sleep(0.1)
        out["degraded_read"] = await _run_ops(
            min(degraded_reads, n_objects), in_flight, read)
        await svc.drain()

        marked = _markdowns_since(cursor)
        if marked != {f"osd.{i}" for i in dead}:
            raise AssertionError(f"marked down {sorted(marked)}, stopped "
                                 f"{dead}")
        out["stopped_osds"] = dead
        out["offload"] = {k: svc.stats[k] for k in (
            "jobs", "batches", "coalesced_ops", "fallback_ops",
            "breaker_trips", "device_failovers", "mesh_batches")}
        out["offload"]["degraded"] = svc.degraded
        perf = svc.perf.dump()
        out["offload"]["kernel_gb_s"] = {
            kind: perf[f"kernel_{kind}_gbps"] for kind in ("enc", "dec")}
        out["devices"] = svc.device_snapshot()
        on_device = [s for d, s in out["devices"].items() if d != "host"]
        out["mean_device_batch_bytes"] = round(
            sum(s["bytes"] for s in on_device)
            / max(1, sum(s["batches"] for s in on_device)))
        out["native_frames"] = frames.native_active()
    return out


def check_served(out: dict, platform: str, min_device_bytes: int) -> None:
    """What the offload counters must show for the run to count: every
    encode and decode byte went through a device of `platform`, none
    through the host codec."""
    off, devices = out["offload"], out["devices"]
    on_device = {d: s for d, s in devices.items()
                 if d.startswith(platform + ":")}
    problems = []
    if off["batches"] <= 0:
        problems.append("no offload batches ran")
    for name in ("fallback_ops", "breaker_trips", "device_failovers"):
        if off[name]:
            problems.append(f"{name}={off[name]}")
    if off["degraded"]:
        problems.append("offload service degraded")
    if set(devices) - set(on_device) - {"host"}:
        problems.append(f"batches booked under {sorted(devices)}")
    # a write's checksums ride its encode (the rider's finisher takes
    # them over the planes it made): nothing is the host lane's
    host = {k: devices.get("host", {}).get(k, 0) for k in ("ops", "bytes")}
    if any(host.values()):
        problems.append(f"host lane holds {host}")
    device_bytes = sum(s["bytes"] for s in on_device.values())
    if device_bytes < min_device_bytes:
        problems.append(f"{device_bytes} bytes under {platform}:*, "
                        f"{min_device_bytes} expected")
    if not off["kernel_gb_s"]["dec"]:
        problems.append("no decode batch reached the device")
    if not out["native_frames"]:
        problems.append("native frame codec not built")
    if problems:
        raise AssertionError("served path: " + "; ".join(problems))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    device = select_device()
    print(f"chip_smoke: {json.dumps(device)}", flush=True)
    cached_at_start = _cache_entries(device)
    t0 = time.perf_counter()
    kernels = phase_kernels(args.seed)
    print(f"chip_smoke: kernels ok "
          f"({time.perf_counter() - t0:.1f}s) {json.dumps(kernels)}",
          flush=True)
    t0 = time.perf_counter()
    # a clean asyncio tail: "Task was destroyed but it is pending" and
    # never-retrieved exceptions both arrive as ERROR records, the
    # former only once the abandoned task is collected
    asyncio_errors: list[str] = []
    handler = logging.Handler(level=logging.ERROR)
    handler.emit = lambda record: asyncio_errors.append(record.getMessage())
    logging.getLogger("asyncio").addHandler(handler)
    served = asyncio.run(phase_served(args.seed))
    gc.collect()
    if asyncio_errors:
        raise AssertionError(f"asyncio reported {len(asyncio_errors)} "
                             f"error(s): {asyncio_errors[:3]}")
    check_served(served, "tpu", min_device_bytes=1 << 30)
    print(f"chip_smoke: served path ok ({time.perf_counter() - t0:.1f}s)",
          flush=True)
    # cold versus warm: a second run with the same cache directory adds
    # no entries and spends less in `compile_s`
    device["compile_cache_entries"] = [cached_at_start,
                                       _cache_entries(device)]
    print(json.dumps({"chip_smoke": {"seed": args.seed, "device": device,
                                     "kernels": kernels, "served": served}}),
          flush=True)
    print(json.dumps({"ok": True, "device": {
        k: device[k] for k in ("platform", "kind", "count")}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
