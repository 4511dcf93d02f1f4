"""Measurement children for bench.py — each stage runs in its own process
so the parent can enforce per-stage wall-clock timeouts and stay off jax
(a chip belongs to one process at a time).

Stages (`python -m ceph_tpu.tools.bench_driver --stage X`):

  cpu     CPU baselines only. The parent runs this under
          JAX_PLATFORMS=cpu so even a transitive jax import cannot
          claim the chip.
            cpu_native_encode   C++ split-table SIMD codec (isa stand-in)
            cpu_native_decode   same kernel, 3-erasure recovery matrix
            cpu_numpy_encode    pure-numpy GF(2^8) matrix apply
            cpu_crc32c          C++ slice-by-8 crc32c over 4 KiB blocks
  probe   `import jax; jax.devices()` and nothing else; prints platform.
          Cheap enough to retry a few times under a short timeout.
  device  Device benches (raises unless jax reports platform "tpu"):
            tpu_encode          batched device-resident encode_stripes
            tpu_decode          batched device-resident decode_stripes
            tpu_crc32c          device crc32c kernel
            tpu_encode_host     batched encode incl. H2D/D2H transfers
            scalar_encode       per-stripe plugin-contract encode()

North-star config throughout: k=8, m=3, chunk = 1 MiB — the reference
`ceph_erasure_code_benchmark -P k=8 -P m=3 -s 8M` geometry
(src/test/erasure-code/ceph_erasure_code_benchmark.cc:186-193,297-324;
GB/s = KiB/2^20/seconds per qa/workunits/erasure-code/bench.sh:214).

Each stage prints exactly one JSON line on stdout; logs go to stderr.
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import re
import sys
import time

import numpy as np

K, M = 8, 3
CHUNK = 1 << 20                    # 1 MiB chunk
SIZE = K * CHUNK                   # 8 MiB stripe buffer
PARAMS = {"k": str(K), "m": str(M)}


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def _bench_into(results: dict, name: str, **kw) -> float:
    from ceph_tpu.tools.ec_benchmark import BenchConfig, run_bench
    cfg = BenchConfig(parameters=dict(PARAMS), size=SIZE,
                      erasures=M, seed=42, **kw)
    r = run_bench(cfg)
    results[name] = round(r.gb_per_s, 4)
    log(f"{name}: {r.gb_per_s:.3f} GB/s ({r.seconds:.3f}s)")
    return r.gb_per_s


def _require_tpu() -> float:
    """Initialise the jax backend; returns init seconds. Raises unless
    the default backend is a TPU: a stage that reports `tpu_*` keys
    never runs on whatever platform jax happened to find."""
    t0 = time.perf_counter()
    import jax
    devices = jax.devices()
    init_s = round(time.perf_counter() - t0, 1)
    platform = devices[0].platform
    log(f"jax backend up: {platform} x{len(devices)} ({init_s}s)")
    if platform != "tpu":
        raise RuntimeError(
            f"this stage measures the device path and needs platform "
            f"'tpu'; jax reports {platform!r}")
    return init_s


def stage_cpu() -> dict:
    results: dict[str, float] = {}
    for name, kw in (
            ("cpu_native_encode", dict(mode="native", workload="encode",
                                       iterations=40, warmup=3)),
            ("cpu_native_decode", dict(mode="native", workload="decode",
                                       iterations=40, warmup=3)),
            ("cpu_numpy_encode", dict(mode="baseline", workload="encode",
                                      iterations=3, warmup=1))):
        try:
            _bench_into(results, name, plugin="isa", **kw)
        except Exception as e:  # host baselines: record and continue
            log(f"{name}: FAILED {type(e).__name__}: {e}")
            results[name] = 0.0
    # crc32c Checksummer host baseline (BASELINE: 4 KiB blocks; the 10^6
    # block scale is reached by iterating the per-call block batch)
    try:
        from ceph_tpu.native import ec_native
        from ceph_tpu.tools.ec_benchmark import _time_host_loop
        nblocks = 1 << 14
        gib = nblocks * 4096 / (1 << 30)
        blocks = np.random.default_rng(0).integers(
            0, 256, (nblocks, 4096), dtype=np.uint8)
        iters = 8
        dt = _time_host_loop(lambda: ec_native.crc32c_blocks(blocks, 4096),
                             iters, 1)
        results["cpu_crc32c"] = round(iters * gib / dt, 4)
        log(f"cpu_crc32c: {results['cpu_crc32c']} GB/s")
    except Exception as e:
        log(f"cpu_crc32c: FAILED {type(e).__name__}: {e}")
        results["cpu_crc32c"] = 0.0
    results.update(_msgr_frame_microbench())
    return results


def _msgr_frame_microbench() -> dict:
    """Messenger frame-codec microbench: whole-frame encode+decode
    round trips per second, native C codec vs the pure-Python fallback,
    over a data-plane-shaped frame (two small JSON segments + one 32
    KiB data segment — the k=8 sub-op shape). The per-frame Python this
    PR removes is exactly the delta between these two rates."""
    out: dict = {}
    try:
        from ceph_tpu.msg import frames
        from ceph_tpu.msg.frames import Frame, Tag
        seg = bytes(range(256)) * 128          # 32 KiB
        frame = Frame(Tag.MESSAGE,
                      [b'{"type":112,"seq":123}', b'{"sub":"x"}' * 8,
                       seg])
        was = frames.native_active()
        try:
            for label, use_native in (("native", True), ("python", False)):
                if use_native and not frames.set_native(True):
                    out["msgr_frames_per_s_native"] = 0.0
                    continue
                frames.set_native(use_native)
                blob = frame.encode()
                n = 4000
                t0 = time.perf_counter()
                for _ in range(n):
                    frame.encode()      # 32 KiB: the write loop packs it
                    Frame.decode(blob)
                rate = n / (time.perf_counter() - t0)
                out[f"msgr_frames_per_s_{label}"] = round(rate, 1)
        finally:
            frames.set_native(was)
        if out.get("msgr_frames_per_s_python"):
            out["msgr_frame_native_speedup"] = round(
                (out.get("msgr_frames_per_s_native") or 0.0)
                / out["msgr_frames_per_s_python"], 3)
        log(f"msgr_frames: native {out.get('msgr_frames_per_s_native')}"
            f"/s python {out.get('msgr_frames_per_s_python')}/s "
            f"(x{out.get('msgr_frame_native_speedup')})")
    except Exception as e:
        log(f"msgr_frames: FAILED {type(e).__name__}: {e}")
    try:
        out.update(_msgr_saturated_batching())
    except Exception as e:
        log(f"msgr_saturated: FAILED {type(e).__name__}: {e}")
    return out


def _msgr_saturated_batching() -> dict:
    """Per-peer batching at connection saturation: a real messenger
    pair over localhost, the sender enqueuing one client EC write's
    worth of data-plane traffic (k=8,m=3: 11 sub-op-sized messages one
    way — the other 11 of the 22 are the mirror direction) faster than
    the wire drains. Reports frames per 11-message write-equivalent —
    the asymptote the in-situ number approaches as per-connection
    queue depth grows (today capped by the per-PG op pipeline)."""
    import asyncio

    from ceph_tpu.msg import messages as M
    from ceph_tpu.msg import messenger as msgr_mod
    from ceph_tpu.msg.messenger import (Dispatcher, Messenger, Policy,
                                        msgr_perf)

    WRITES, PER_WRITE = 200, 11

    async def body() -> dict:
        got = [0]
        done = asyncio.Event()

        class Sink(Dispatcher):
            async def ms_dispatch(self, conn, msg):
                if isinstance(msg, M.MOSDECSubOpWrite):
                    got[0] += 1
                    if got[0] >= WRITES * PER_WRITE:
                        done.set()
                    return True
                return False

        srv = Messenger("bench-msgr-srv")
        srv.add_dispatcher(Sink())
        addr = await srv.bind("127.0.0.1", 0)
        cli = Messenger("bench-msgr-cli")
        conn = await cli.connect(addr, Policy.lossless_peer())
        pc = msgr_perf()
        base = dict(pc.dump())
        payload = bytes(4096)
        t0 = time.perf_counter()
        for w in range(WRITES):
            for s in range(PER_WRITE):
                conn.send_message(M.MOSDECSubOpWrite(
                    {"tid": w, "shard": s}, payload))
            if w % 8 == 0:
                await asyncio.sleep(0)      # let the write loop drain
        await asyncio.wait_for(done.wait(), 30)
        dt = time.perf_counter() - t0
        d = {k: v - base[k] for k, v in pc.dump().items()
             if isinstance(v, int) and k in base}
        await cli.shutdown()
        await srv.shutdown()
        frames_per_write = d["data_frames_tx"] / WRITES
        return {
            "msgr_saturated_frames_per_write": round(frames_per_write, 2),
            "msgr_saturated_msgs_per_s": round(
                WRITES * PER_WRITE / dt, 1),
        }

    enabled = msgr_mod._BATCH_DEFAULTS["enabled"]
    try:
        msgr_mod._BATCH_DEFAULTS["enabled"] = True
        out = asyncio.run(body())
    finally:
        msgr_mod._BATCH_DEFAULTS["enabled"] = enabled
    log(f"msgr_saturated: {out['msgr_saturated_frames_per_write']} "
        f"frames per 11-msg write-equivalent at "
        f"{out['msgr_saturated_msgs_per_s']} msgs/s")
    return out


def stage_probe() -> dict:
    t0 = time.perf_counter()
    import jax
    devices = jax.devices()
    return {
        "platform": devices[0].platform,
        "device_count": len(devices),
        "init_s": round(time.perf_counter() - t0, 1),
    }


def stage_device() -> dict:
    t0 = time.perf_counter()
    init_s = _require_tpu()
    import jax
    batch, iters = 16, 40

    results: dict[str, float] = {"platform": "tpu",
                                 "backend_init_s": init_s}
    _bench_into(results, "tpu_encode", plugin="tpu", mode="batched",
                workload="encode", batch=batch, iterations=iters, warmup=2)
    _bench_into(results, "tpu_decode", plugin="tpu", mode="batched",
                workload="decode", batch=batch, iterations=iters, warmup=2)

    # Device memory-bandwidth peak: a saturating on-device elementwise
    # sweep (read + write of a large resident buffer) — the roofline
    # every codec GB/s is judged against. The guarded number below is
    # tpu_encode as a PERCENT of this same-run peak: the r04->r05
    # 35.2->32.0 slide re-baselined so backend/container drift that
    # moves both numbers together no longer reads as a codec
    # regression.
    import jax.numpy as jnp
    nbytes = 256 << 20
    arr = jnp.zeros(nbytes // 4, dtype=jnp.float32)
    sweep_f = jax.jit(lambda x: x + 1.0)
    jax.block_until_ready(sweep_f(arr))            # compile + warm
    peak_iters = 10
    times = []
    for _ in range(peak_iters):
        t1 = time.perf_counter()
        jax.block_until_ready(sweep_f(arr))
        times.append(time.perf_counter() - t1)
    times.sort()
    # read + write per element
    peak = round(2 * nbytes / times[len(times) // 2] / 1e9, 2)
    results["device_peak_gbps"] = peak
    results["tpu_encode_roofline_pct"] = round(
        100.0 * results["tpu_encode"] / peak, 2)
    log(f"device_peak: {peak} GB/s (elementwise sweep, median of "
        f"{peak_iters}); tpu_encode at "
        f"{results['tpu_encode_roofline_pct']}% of peak")

    from ceph_tpu.ops import crc32c as crc_dev
    from ceph_tpu.tools.ec_benchmark import (_device_test_data,
                                             _time_device_loop)
    nblocks = 1 << 16
    gib = nblocks * 4096 / (1 << 30)
    dev_crc = crc_dev.get_device_crc(4096)
    # generated on device: the working set never crosses the link
    dev_blocks = _device_test_data(nblocks, 1, 4096).reshape(nblocks, 4096)
    crc_iters = 16
    dt = _time_device_loop(lambda: dev_crc(dev_blocks), crc_iters, 2)
    results["tpu_crc32c"] = round(crc_iters * gib / dt, 4)
    log(f"tpu_crc32c: {results['tpu_crc32c']} GB/s "
        f"({crc_iters * nblocks} blocks total)")

    # Raw link bandwidth: how fast CAN bytes move host->device here? It
    # is the hard ceiling on ANY host-buffer codec number, so it is
    # measured and reported alongside them, the way the offload service
    # actually transfers: the SAME host staging buffer reused across
    # dispatches. The old single cold transfer charged first-touch page
    # faults and allocator work to the link, understating the
    # achievable rate and skewing the attribution waterfall's H2D
    # bucket.
    mb = 32
    buf = np.zeros(mb << 20, dtype=np.uint8)
    jax.block_until_ready(jax.device_put(buf[:1024]))   # warm path
    t1 = time.perf_counter()
    jax.block_until_ready(jax.device_put(buf))
    results["link_h2d_cold_gbps"] = round(
        (mb / 1024) / (time.perf_counter() - t1), 4)
    times = []
    for _ in range(5):
        t2 = time.perf_counter()
        jax.block_until_ready(jax.device_put(buf))
        times.append(time.perf_counter() - t2)
    times.sort()
    results["link_h2d_gbps"] = round(
        (mb / 1024) / times[len(times) // 2], 4)
    log(f"link_h2d: {results['link_h2d_gbps']} GB/s steady "
        f"(reused staging buffer, median of {len(times)}), "
        f"{results['link_h2d_cold_gbps']} GB/s cold ({mb} MiB)")

    # Host-buffer paths pay H2D/D2H; they can never beat link_h2d_gbps.
    # The reported efficiency (host encode / link ceiling) is the
    # meaningful figure — the device-resident numbers above are the
    # capability measurement.
    _bench_into(results, "tpu_encode_host", plugin="tpu", mode="batched-host",
                workload="encode", batch=16, iterations=2, warmup=1)
    results["host_encode_link_efficiency"] = round(
        results["tpu_encode_host"] / results["link_h2d_gbps"], 3)
    _bench_into(results, "scalar_encode", plugin="tpu", mode="scalar",
                workload="encode", iterations=2, warmup=1)
    # real multi-chip backend: this stage carries the authoritative
    # device-count scaling curve (cluster_tpu's virtual-device child
    # fills it in on single-device backends)
    if len(jax.devices()) >= 2:
        results.update(_mesh_scaling_body())
    results["elapsed_s"] = round(time.perf_counter() - t0, 1)
    return results


def stage_cluster() -> dict:
    """In-situ cluster throughput (the `rados bench` analog, r4 verdict
    #5): N concurrent writers/readers through the full client->mon->osd
    ->PG->backend stack on localhost sockets, replicated AND EC pools.
    Runs on the CPU jax backend (it measures the FRAMEWORK, not the
    codec device)."""
    import asyncio

    results: dict = {}

    async def body():
        import argparse
        from ceph_tpu.tools.rados_bench import _main
        for pool_type, k, m in (("replicated", 0, 0), ("erasure", 2, 2)):
            args = argparse.Namespace(
                seconds=4.0, concurrency=8, object_size=256 * 1024,
                pool_type=pool_type, plugin="jerasure", k=k, m=m,
                osds=4, backend="memstore")
            out = await _main(args)
            key = "cluster_rep" if pool_type == "replicated" \
                else "cluster_ec"
            results[f"{key}_write_mb_s"] = out["write"]["mb_per_s"]
            results[f"{key}_read_mb_s"] = out["read"]["mb_per_s"]
            results[f"{key}_write_p99_ms"] = out["write"]["lat_p99_ms"]
            results[f"{key}_read_p99_ms"] = out["read"]["lat_p99_ms"]
            log(f"{key}: write {out['write']['mb_per_s']} MB/s "
                f"read {out['read']['mb_per_s']} MB/s")

    async def probe_health():
        """One observability pass: boot a full cluster (mgr + mds +
        rgw), let the report fan-in converge, then record the mon
        health and the exporter's per-daemon labels so BENCH_r*.json
        shows degradation alongside throughput."""
        import re
        import tempfile

        from ceph_tpu.tools.vstart import VCluster
        with tempfile.TemporaryDirectory(prefix="bench-health-") as base:
            c = VCluster(base, n_mons=1, n_osds=3, with_mgr=True,
                         with_mds=True, with_rgw=True)
            try:
                await c.start()
                loop = asyncio.get_running_loop()
                deadline = loop.time() + 30
                want = {"osd", "mon", "mds", "rgw"}
                while want - {st.service for st in
                              c.mgr.daemon_index.daemons.values()}:
                    if loop.time() > deadline:
                        break
                    await asyncio.sleep(0.25)
                health = await c.mgr.mon_command({"prefix": "health"})
                reader, writer = await asyncio.open_connection(
                    *c.mgr.exporter.addr)
                writer.write(b"GET /metrics HTTP/1.0\r\n\r\n")
                await writer.drain()
                blob = await reader.read()
                writer.close()
                text = blob.split(b"\r\n\r\n", 1)[1].decode()
                results["health"] = {
                    # the probe boots its own full cluster (rados_bench
                    # tears its benchmark cluster down internally): this
                    # records the observability plane converging, not
                    # the bench cluster's load response
                    "scope": "post-bench observability probe "
                             "(fresh 3-osd + mgr/mds/rgw cluster)",
                    "status": health.get("status"),
                    "checks": sorted(health.get("checks", {})),
                    "daemon_report_ages":
                        c.mgr.daemon_index.report_ages(),
                    "metric_daemons": sorted(
                        set(re.findall(r'ceph_daemon="([^"]+)"', text))),
                    "metric_lines": sum(
                        1 for ln in text.splitlines()
                        if ln.startswith("ceph_")),
                }
                log(f"health: {results['health']['status']} "
                    f"checks={results['health']['checks']} "
                    f"daemons={results['health']['metric_daemons']}")
            finally:
                await c.stop()
    asyncio.run(body())
    try:
        asyncio.run(asyncio.wait_for(probe_health(), 120))
    except Exception as e:
        results["health"] = {"status": f"probe failed: "
                                       f"{type(e).__name__}: {e}"}
    return results


# -- mesh scaling curve -------------------------------------------------------

SCALING_COUNTS = (1, 2, 4, 8)

#: reactor shard counts the cluster_tpu stage sweeps (capped by the
#: CEPH_TPU_REACTOR_SHARDS knob bench.py passes through)
REACTOR_SHARD_COUNTS = (1, 2, 4)


def _reactor_shards_knob(default: int = 4) -> int:
    """The bench's reactor_shards knob (CEPH_TPU_REACTOR_SHARDS)."""
    try:
        return max(1, int(os.environ.get("CEPH_TPU_REACTOR_SHARDS",
                                         str(default))))
    except ValueError:
        return default


#: process-backed reactor worker counts the cluster_tpu stage sweeps
#: (capped by the CEPH_TPU_REACTOR_PROCS knob and the core count)
REACTOR_PROC_COUNTS = (1, 2)


def _reactor_procs_knob(default: int = 2) -> int:
    """The bench's reactor_procs knob (CEPH_TPU_REACTOR_PROCS)."""
    try:
        return max(1, int(os.environ.get("CEPH_TPU_REACTOR_PROCS",
                                         str(default))))
    except ValueError:
        return default


def _mesh_scaling_body() -> dict:
    """Device-count scaling of the sharded stripe encode (the offload
    service's oversized-batch path): the SAME fixed workload timed over
    1/2/4/8-device meshes via parallel.sharded_apply_fn, plus a
    bit-identity check of the widest mesh against the 1-device result.

    scaling_efficiency is normalized by the parallelism the hardware
    can actually deliver: on real multi-chip meshes that is the device
    count; on virtual host devices (xla_force_host_platform_device_count
    carving one CPU into 8 "devices") it is capped at the core count —
    8 virtual devices on 2 cores can never beat 2x, and pretending the
    ideal is 8x would make the number meaningless. The raw (device-
    normalized) efficiency is reported alongside, labeled."""
    import jax

    from ceph_tpu.ec import gf256
    from ceph_tpu.parallel import mesh as mesh_lib

    devs = jax.devices()
    platform = devs[0].platform
    counts = [c for c in SCALING_COUNTS if c <= len(devs)]
    K8, M3 = 8, 3
    C = 1 << 16                      # 64 KiB chunks
    B = max(8, counts[-1])           # fixed total work (strong scaling)
    rng = np.random.default_rng(7)
    data = rng.integers(0, 256, (B, K8, C), dtype=np.uint8)
    coding = gf256.reed_sol_van_matrix(K8, M3)
    curve: dict[str, float] = {}
    outputs: dict[int, np.ndarray] = {}
    for n in counts:
        # stripe-only meshes, matching the offload service's serving
        # mesh: the stripe axis is pure data parallelism (no all-gather,
        # no padded parity rows), which is what the fan-out scales over
        mesh = mesh_lib.make_mesh(n, stripe=n, shard_max=1)
        fn = mesh_lib.sharded_apply_fn(mesh, coding)
        outputs[n] = np.asarray(fn(data))        # compile + warm
        times = []
        for _ in range(3):
            t0 = time.perf_counter()
            fn(data)
            times.append(time.perf_counter() - t0)
        times.sort()
        gbps = B * K8 * C / times[len(times) // 2] / 1e9
        curve[str(n)] = round(gbps, 4)
        log(f"mesh_scaling: {n} device(s) "
            f"{dict(mesh.shape)} -> {curve[str(n)]} GB/s")
    n_max = counts[-1]
    bit_identical = bool(np.array_equal(outputs[n_max], outputs[counts[0]]))
    try:
        cores = len(os.sched_getaffinity(0))
    except (AttributeError, OSError):
        cores = os.cpu_count() or 1
    virtual = platform == "cpu"      # host devices share the host cores
    ideal = min(n_max, cores) if virtual else n_max
    g1, gn = curve[str(counts[0])], curve[str(n_max)]
    out = {
        "device_scaling_gb_s": curve,
        "scaling_devices": n_max,
        "scaling_platform": platform,
        "scaling_virtual_devices": virtual,
        "scaling_ideal_parallelism": ideal,
        "scaling_bit_identical": bit_identical,
        "scaling_efficiency_raw": round(gn / (n_max * g1), 4)
        if g1 else 0.0,
        "scaling_efficiency": round(gn / (ideal * g1), 4)
        if g1 else 0.0,
    }
    log(f"mesh_scaling: efficiency {out['scaling_efficiency']} "
        f"(ideal x{ideal}, raw {out['scaling_efficiency_raw']} over "
        f"{n_max} {'virtual ' if virtual else ''}devices), "
        f"bit_identical={bit_identical}")
    return out


def stage_mesh_scaling() -> dict:
    """Child entry for the scaling curve (spawned with
    xla_force_host_platform_device_count when the parent's backend has
    a single device)."""
    return _mesh_scaling_body()


def stage_proc_scaling() -> dict:
    """Process-backed reactor scaling: a k=8,m=3 EC write workload of
    one-stripe objects with the OSDs forked into 1/2 WORKER PROCESSES
    (utils/reactor.py ProcShardPool — mon/client stay in this process
    on shard 0). This is the true GIL escape the thread curve could
    never show (1->2 threads measured 0.74x): each worker runs its own
    interpreter and its own loop, and the data path crosses the process
    boundary over the messenger's existing sockets. The pool runs the
    HOST plugin (isa) and cluster_tpu runs this stage as a CPU child:
    a chip belongs to one process, even a host plugin brings up a jax
    backend in every worker (MatrixCodec pins its bitmatrix at init),
    and the curve measures the GIL escape, not the codec.
    Capped at the core count like the shard curve; bit-identity is
    checked by reading a known object back under every count. The
    widest run arms the loop profiler in EVERY process (config
    propagation over the control channel) and records the
    cross-process shard_busy_skew the trend guard watches."""
    import asyncio

    from ceph_tpu.tools.cluster_boot import ephemeral_cluster
    from ceph_tpu.tools.rados_bench import _phase
    from ceph_tpu.utils import loopprof

    results: dict = {}
    K8, M3 = 8, 3
    OBJ = K8 * 4096              # one stripe: the worst-case tiny op
    CONC = 16

    async def procs_curve():
        max_procs = _reactor_procs_knob()
        try:
            cores = len(os.sched_getaffinity(0))
        except (AttributeError, OSError):
            cores = os.cpu_count() or 1
        proc_counts = [n for n in REACTOR_PROC_COUNTS
                       if n <= max_procs and n <= max(cores, 1)] or [1]
        curve: dict[str, float] = {}
        identical = True
        payload = bytes(range(256)) * (OBJ // 256)
        for n in proc_counts:
            async with ephemeral_cluster(
                    K8 + M3, prefix=f"bench-proc{n}-",
                    reactor_procs=n) as (client, osds, _mon):
                await client.command({
                    "prefix": "osd erasure-code-profile set",
                    "name": "hostprof",
                    "profile": {"plugin": "isa", "k": str(K8),
                                "m": str(M3)}})
                await client.pool_create("procbench", pg_num=8,
                                         pool_type="erasure",
                                         erasure_code_profile="hostprof")
                io = client.ioctx("procbench")
                await asyncio.gather(*[io.write_full(f"warm-{i}", payload)
                                       for i in range(4)])
                pool = osds[0].pool
                profiled = n == proc_counts[-1]
                try:
                    if profiled:
                        loopprof.install()      # parent shard 0
                        await pool.config_set("profiler_enabled", True)
                    counts: dict = {}
                    w = await _phase(io, "write", CONC, 2.5, OBJ,
                                     counts)
                    if profiled:
                        prof = await pool.profile_stats()
                        results["reactor_proc_per_shard"] = \
                            prof["shards"]
                        results["shard_busy_skew_procs"] = \
                            prof["shard_busy_skew"]
                finally:
                    if profiled:
                        # unarm even on a failed iteration: a sampler
                        # left installed would tax every later stage
                        try:
                            await pool.config_set("profiler_enabled",
                                                  False)
                        except Exception:
                            pass
                        loopprof.uninstall()
                curve[str(n)] = w["mb_per_s"]
                got = await io.read("warm-0")
                identical = identical and got == payload
                log(f"reactor_procs={n}: write {w['mb_per_s']} MB/s "
                    f"(bit_identical={got == payload})")
        results["reactor_proc_scaling_mb_s"] = curve
        results["reactor_proc_bit_identical"] = identical
        results["reactor_procs"] = proc_counts[-1]
        results["reactor_proc_cores"] = cores
        base = curve.get("1") or 0.0
        results["reactor_proc_speedup"] = round(
            curve[str(proc_counts[-1])] / base, 3) if base else 0.0
        # the guarded in-situ number: EC write MB/s with the widest
        # process fan-out (acceptance: >= 1.15x the 1-proc figure on a
        # 2-core box, where 2 THREADS measured 0.74x)
        results["cluster_ec_write_mb_s_procs"] = \
            curve[str(proc_counts[-1])]
        log(f"reactor_proc_scaling: {curve} "
            f"(speedup x{results['reactor_proc_speedup']}, "
            f"skew={results.get('shard_busy_skew_procs')}, "
            f"bit_identical={identical})")

    asyncio.run(asyncio.wait_for(procs_curve(), 240))
    return results


def _cpu_child(stage: str, timeout: float, virtual_devices: int = 0) -> dict:
    """Run one stage of this module in a child held to the CPU backend
    (with `virtual_devices` XLA host devices when given) and return its
    JSON; raises if the child fails. A stage that holds the chip uses
    this for whatever needs a jax backend of its own."""
    import subprocess

    repo = os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))
    env = dict(os.environ)
    env.pop("JAX_PLATFORM_NAME", None)
    env["JAX_PLATFORMS"] = "cpu"
    if virtual_devices:
        env["XLA_FLAGS"] = (
            env.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={virtual_devices}")
    env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run(
        [sys.executable, "-m", "ceph_tpu.tools.bench_driver",
         "--stage", stage],
        cwd=repo, env=env, capture_output=True, text=True, timeout=timeout)
    sys.stderr.write(proc.stderr)
    if proc.returncode != 0:
        raise RuntimeError(f"{stage} child exited {proc.returncode}")
    return json.loads(proc.stdout.strip().splitlines()[-1])


def _device_scaling_curve() -> dict:
    """The scaling curve via a hermetic 8-virtual-device child — only
    for single-device backends (on real multi-chip hardware the device
    stage already ran _mesh_scaling_body in-process, and its keys win
    the bench.py detail merge; running it again here would double the
    mesh compile + timing cost per round)."""
    import jax
    if len(jax.devices()) >= 2:
        log("mesh_scaling: skipped (device stage covers multi-device "
            "backends)")
        return {}
    try:
        return _cpu_child("mesh_scaling", 180, virtual_devices=8)
    except Exception as e:
        log(f"mesh_scaling child: FAILED {type(e).__name__}: {e}")
        return {}


def stage_cluster_tpu() -> dict:
    """Cluster-EC-over-tpu (the round-5 gap: "the TPU plugin still never
    serves the in-situ cluster data path"): a real mon + 11-osd cluster,
    EC pool plugin=tpu k=8 m=3 (north-star profile), small one-stripe
    objects so every PG op is exactly the tiny per-op encode the verdict
    indicts. Two timed passes over the same stack:

      inline   ec_offload_enabled=false — each op dispatches its own
               synchronous device encode (the pre-offload behavior);
      offload  the offload service coalesces concurrent PG ops into
               staged device batches.

    Reports both write throughputs, their ratio, and the offload batch
    stats (mean device batch size, coalesced ops, fallbacks) so
    BENCH_r*.json finally tracks the in-situ number per round."""
    import asyncio
    import time as _t

    t0 = _t.perf_counter()
    _require_tpu()

    results: dict = {"cluster_ec_tpu_platform": "tpu"}
    K8, M3 = 8, 3
    OBJ = K8 * 4096              # one stripe: the worst-case tiny op
    SECONDS, CONC = 3.0, 16

    async def body():
        from ceph_tpu import offload
        from ceph_tpu.tools.cluster_boot import ephemeral_cluster
        from ceph_tpu.tools.rados_bench import _phase

        async with ephemeral_cluster(K8 + M3, prefix="bench-tpu-") \
                as (client, osds, _mon):
            try:
                await client.command({
                    "prefix": "osd erasure-code-profile set",
                    "name": "tpuprof",
                    "profile": {"plugin": "tpu", "k": str(K8), "m": str(M3)}})
                await client.pool_create("benchtpu", pg_num=8,
                                         pool_type="erasure",
                                         erasure_code_profile="tpuprof")
                io = client.ioctx("benchtpu")
                svc = offload.get_service()
                # warm both paths: compiles the batch-bucket XLA programs
                # outside the timed windows
                payload = bytes(OBJ)
                for enabled in (True, False):
                    offload.set_enabled(enabled)
                    await asyncio.gather(*[io.write_full(f"warm-{enabled}-{i}",
                                                         payload)
                                           for i in range(4)])
                phases = {}
                for name, enabled in (("inline", False), ("offload", True)):
                    offload.set_enabled(enabled)
                    base = dict(svc.stats)
                    counts: dict = {}
                    w = await _phase(io, "write", CONC, SECONDS, OBJ, counts)
                    r = await _phase(io, "read", CONC, SECONDS, OBJ, counts)
                    d = {k: svc.stats[k] - base[k] for k in base}
                    phases[name] = (w, r, d)
                    log(f"cluster_ec_tpu[{name}]: write "
                        f"{w['mb_per_s']} MB/s read {r['mb_per_s']} MB/s "
                        f"batches={d['batches']} "
                        f"coalesced={d['coalesced_ops']} "
                        f"fallbacks={d['fallback_ops']}")
                wo, ro, do = phases["offload"]
                wi, _ri, _di = phases["inline"]
                results["cluster_ec_tpu_write_mb_s"] = wo["mb_per_s"]
                results["cluster_ec_tpu_read_mb_s"] = ro["mb_per_s"]
                results["cluster_ec_tpu_write_p99_ms"] = wo["lat_p99_ms"]
                results["cluster_ec_tpu_inline_write_mb_s"] = wi["mb_per_s"]
                results["cluster_ec_tpu_offload_vs_inline"] = round(
                    wo["mb_per_s"] / wi["mb_per_s"], 3) \
                    if wi["mb_per_s"] else 0.0
                results["offload_batches"] = do["batches"]
                results["offload_mean_batch_ops"] = round(
                    do["batched_ops"] / do["batches"], 3) \
                    if do["batches"] else 0.0
                results["offload_coalesced_ops"] = do["coalesced_ops"]
                results["offload_fallback_ops"] = do["fallback_ops"]
                results["offload_status"] = osds[0]._offload_admin("status")

                # frames per client EC write (k=8,m=3), from the msgr
                # perf counters: many PGs + deep client concurrency so
                # per-OSD fan-outs overlap and coalesce per peer conn —
                # pre-batching this was 22 frames/write (1 op + 10
                # sub-ops + 10 replies + 1 reply). data_frames counts
                # only the data plane, so heartbeats/mgr reports don't
                # pollute the figure. (The per-PG op pipeline serializes
                # each PG's writes, which caps per-connection queue
                # depth — the saturated-connection asymptote lives in
                # the cpu stage's msgr microbench; ROADMAP names PG op
                # pipelining as the next lever.)
                from ceph_tpu.msg.messenger import msgr_perf
                await client.pool_create("msgrbench", pg_num=32,
                                         pool_type="erasure",
                                         erasure_code_profile="tpuprof")
                iom = client.ioctx("msgrbench")
                await asyncio.gather(*[iom.write_full(f"w{i}", payload)
                                       for i in range(8)])
                pc = msgr_perf()
                base_m = dict(pc.dump())
                counts2: dict = {}
                wm = await _phase(iom, "write", 128, 2.0, OBJ, counts2)
                dm = {k: v - base_m[k] for k, v in pc.dump().items()
                      if isinstance(v, int) and k in base_m}
                ops = max(1, wm["ops"])
                results["msgr_frames_per_ec_write"] = round(
                    dm.get("data_frames_tx", 0) / ops, 2)
                results["msgr_batches"] = dm.get("batches_tx", 0)
                results["msgr_batched_msgs"] = dm.get("batched_msgs", 0)
                results["msgr_batch_write_mb_s"] = wm["mb_per_s"]
                results["msgr_mean_batch_msgs"] = round(
                    dm.get("batched_msgs", 0)
                    / dm.get("batches_tx", 1), 2) \
                    if dm.get("batches_tx") else 0.0
                log(f"msgr_batch: {results['msgr_frames_per_ec_write']} "
                    f"data frames/write over {ops} deep-queue writes "
                    f"({results['msgr_batch_write_mb_s']} MB/s, "
                    f"mean batch {results['msgr_mean_batch_msgs']} "
                    f"msgs)")
            finally:
                offload.set_enabled(True)

    async def datapath():
        # EC write DATA PATH in isolation (the encode dispatch pipeline
        # the service rewired), under cluster-shaped concurrency but in
        # a clean loop — measuring it with live daemons starves their
        # heartbeats and churns the cluster mid-window. This is where
        # per-op dispatch overhead lives, undiluted by the Python
        # messaging stack dominating the full-cluster numbers above. On
        # device hardware the inline path pays launch + H2D per tiny
        # op; batching amortizes both.
        from ceph_tpu import offload
        from ceph_tpu.ec import registry as _ecreg
        from ceph_tpu.osd import ec_util as _ecu
        impl = _ecreg.factory("tpu", {"k": str(K8), "m": str(M3)})
        sinfo = _ecu.StripeInfo(K8, OBJ)
        svc = offload.get_service()
        svc.linger_ms = 1.0
        dp_payload = bytes(range(256)) * (OBJ // 256)

        async def dp_phase(enabled, seconds=2.5, conc=32):
            offload.set_enabled(enabled)
            for _ in range(3):          # compile outside the window
                await _ecu.encode_async(sinfo, impl, dp_payload,
                                        service=svc)
            done = [0]
            loop = asyncio.get_running_loop()
            stop = loop.time() + seconds
            t0 = loop.time()

            async def worker():
                while loop.time() < stop:
                    await _ecu.encode_async(sinfo, impl, dp_payload,
                                            service=svc)
                    done[0] += 1
            await asyncio.gather(*[worker() for _ in range(conc)])
            return round(done[0] * OBJ / (loop.time() - t0) / 1e6, 2)

        try:
            dp_inline = await dp_phase(False)
            dp_off = await dp_phase(True)
        finally:
            offload.set_enabled(True)
        results["ec_datapath_inline_mb_s"] = dp_inline
        results["ec_datapath_offload_mb_s"] = dp_off
        results["ec_datapath_offload_vs_inline"] = round(
            dp_off / dp_inline, 3) if dp_inline else 0.0
        log(f"ec_datapath: inline {dp_inline} MB/s, offload "
            f"{dp_off} MB/s "
            f"({results['ec_datapath_offload_vs_inline']}x)")

    async def pipeline_sweep():
        """osd_pg_pipeline_depth sweep over the SAME deep-queue
        workload (pg=8, conc=128, one-stripe objects): depth=1 is the
        old serial per-PG pipeline (windowed admission takes the
        legacy inline path, bit-identical by construction — checked by
        reading a known object back at every depth), and each step up
        lets one PG run that many client ops to distinct objects
        concurrently. Records write MB/s, data frames per EC write
        (deeper per-peer queues => better per-frame amortization of
        PR-12's batches), the offload batcher's mean batch size
        (concurrent stripes finally coalesce), and the window-full
        stall fraction (guarded: a rising stall fraction means the
        window, not the wire, is the new ceiling)."""
        from ceph_tpu import offload
        from ceph_tpu.msg.messenger import msgr_perf
        from ceph_tpu.tools.cluster_boot import ephemeral_cluster
        from ceph_tpu.tools.rados_bench import _phase

        DEPTHS = (1, 2, 4, 8)
        CONC_DEEP = 128
        sweep: dict[str, float] = {}
        frames: dict[str, float] = {}
        batch: dict[str, float] = {}
        stalls: dict[str, float] = {}
        readbacks: dict[int, bytes] = {}
        payload = bytes(range(256)) * (OBJ // 256)
        offload.set_enabled(True)
        for depth in DEPTHS:
            # a FRESH cluster per depth: one shared cluster ages across
            # the sweep (log windows fill, stores grow), handicapping
            # whichever depth runs last — the shard curve isolates its
            # points the same way
            async with ephemeral_cluster(
                    K8 + M3, prefix=f"bench-pipe{depth}-") \
                    as (client, osds, _mon):
                await client.command({
                    "prefix": "osd erasure-code-profile set",
                    "name": "tpuprof",
                    "profile": {"plugin": "tpu", "k": str(K8),
                                "m": str(M3)}})
                await client.pool_create("pipebench", pg_num=8,
                                         pool_type="erasure",
                                         erasure_code_profile="tpuprof")
                io = client.ioctx("pipebench")
                svc = offload.get_service()
                pc = msgr_perf()
                for o in osds:
                    o.config.set("osd_pg_pipeline_depth", depth)
                await asyncio.gather(*[io.write_full(f"warm-{i}",
                                                     payload)
                                       for i in range(4)])
                base_m = dict(pc.dump())
                base_s = dict(svc.stats)
                base_stalls = sum(o.op_queue.window_stalls for o in osds)
                counts: dict = {}
                w = await _phase(io, "write", CONC_DEEP, 2.0, OBJ, counts)
                dm = {k: v - base_m[k] for k, v in pc.dump().items()
                      if isinstance(v, int) and k in base_m}
                ds = {k: svc.stats[k] - base_s[k] for k in base_s}
                ops = max(1, w["ops"])
                d = str(depth)
                sweep[d] = w["mb_per_s"]
                frames[d] = round(dm.get("data_frames_tx", 0) / ops, 2)
                batch[d] = round(ds["batched_ops"] / ds["batches"], 3) \
                    if ds.get("batches") else 0.0
                stalls[d] = round(
                    (sum(o.op_queue.window_stalls for o in osds)
                     - base_stalls) / ops, 4)
                await io.write_full("bitcheck", payload)
                readbacks[depth] = bytes(await io.read("bitcheck"))
                log(f"pipeline_depth={depth}: write {w['mb_per_s']} "
                    f"MB/s, {frames[d]} frames/write, mean offload "
                    f"batch {batch[d]}, stall fraction {stalls[d]}")
        identical = all(rb == readbacks[DEPTHS[0]] == payload
                        for rb in readbacks.values())
        results["pipeline_depth_sweep_mb_s"] = sweep
        results["pipeline_msgr_frames_per_ec_write"] = frames
        results["pipeline_offload_mean_batch_ops"] = batch
        results["pipeline_stall_fraction_by_depth"] = stalls
        results["pipeline_bit_identical"] = identical
        base = sweep.get("1") or 0.0
        results["pipeline_speedup_4v1"] = round(
            (sweep.get("4") or 0.0) / base, 3) if base else 0.0
        # the guarded figures, taken at the DEFAULT depth (4): window
        # stall fraction (rise = the window is the new ceiling) rides
        # next to cluster_ec_write_mb_s / offload_mean_batch_ops
        results["pg_pipeline_stall_fraction"] = stalls.get("4", 0.0)
        log(f"pipeline_sweep: {sweep} (4v1 "
            f"x{results['pipeline_speedup_4v1']}, "
            f"bit_identical={identical})")

    async def shard_curve():
        """Reactor shard scaling: the SAME offload-batched EC write
        workload over 1/2/4-shard reactor runtimes (utils/reactor.py).
        One Python event loop is the cluster-wide ceiling the PR-6
        attribution stage indicted (loop_busy_fraction ~1); this curve
        is the direct measurement of buying loops. Bit-identity is
        checked by reading back a known object under every shard
        count."""
        from ceph_tpu import offload
        from ceph_tpu.tools.cluster_boot import ephemeral_cluster
        from ceph_tpu.tools.rados_bench import _phase

        max_shards = _reactor_shards_knob()
        try:
            cores = len(os.sched_getaffinity(0))
        except (AttributeError, OSError):
            cores = os.cpu_count() or 1
        # cap at the core count, the same deliverable-parallelism rule
        # the mesh curve uses: reactor shards are busy loop THREADS,
        # and oversubscribing them measures GIL/scheduler convoying
        # (ops time out and resend), not shard scaling — on a 2-core
        # box the 4-shard point collapsed ~6x for exactly that reason
        shard_counts = [n for n in REACTOR_SHARD_COUNTS
                        if n <= max_shards and n <= max(cores, 1)] or [1]
        results["reactor_shard_cores"] = cores
        curve: dict[str, float] = {}
        identical = True
        payload = bytes(range(256)) * (OBJ // 256)
        offload.set_enabled(True)
        for n in shard_counts:
            async with ephemeral_cluster(
                    K8 + M3, prefix=f"bench-shard{n}-",
                    reactor_shards=n) as (client, _osds, _mon):
                await client.command({
                    "prefix": "osd erasure-code-profile set",
                    "name": "tpuprof",
                    "profile": {"plugin": "tpu", "k": str(K8),
                                "m": str(M3)}})
                await client.pool_create("shardbench", pg_num=8,
                                         pool_type="erasure",
                                         erasure_code_profile="tpuprof")
                io = client.ioctx("shardbench")
                await asyncio.gather(*[io.write_full(f"warm-{i}", payload)
                                       for i in range(4)])
                counts: dict = {}
                w = await _phase(io, "write", CONC, 2.5, OBJ, counts)
                curve[str(n)] = w["mb_per_s"]
                got = await io.read("warm-0")
                identical = identical and got == payload
                log(f"reactor_shards={n}: write {w['mb_per_s']} MB/s "
                    f"(bit_identical={got == payload})")
        results["reactor_shard_scaling_mb_s"] = curve
        results["reactor_shard_bit_identical"] = identical
        results["reactor_shards"] = shard_counts[-1]
        base = curve.get("1") or 0.0
        results["reactor_shard_speedup"] = round(
            curve[str(shard_counts[-1])] / base, 3) if base else 0.0
        # the guarded in-situ number: EC write MB/s at the widest shard
        # count (the 1-shard figure stays in the curve for the ratio)
        results["cluster_ec_tpu_write_mb_s_sharded"] = \
            curve[str(shard_counts[-1])]
        log(f"reactor_shard_scaling: {curve} "
            f"(speedup x{results['reactor_shard_speedup']}, "
            f"bit_identical={identical})")

    asyncio.run(asyncio.wait_for(body(), 240))
    asyncio.run(asyncio.wait_for(datapath(), 120))
    asyncio.run(asyncio.wait_for(pipeline_sweep(), 180))
    asyncio.run(asyncio.wait_for(shard_curve(), 180))
    # process-backed reactor curve: this process holds the chip, so the
    # worker processes boot under an explicit CPU child
    results.update(_cpu_child("proc_scaling", 300))
    # device-count scaling curve of the mesh fan-out path (1/2/4/8)
    results.update(_device_scaling_curve())
    results["elapsed_s"] = round(_t.perf_counter() - t0, 1)
    return results


# -- failure storm: degraded operation + bandwidth-optimal recovery -----------

def stage_failure_storm() -> dict:
    """The degraded-operation story a production store is judged on,
    measured end to end on a live cluster (ROADMAP failure-storm item):

    Phase A (storm): 11 OSDs, EC pool plugin=clay k=8 m=3 d=10
    (regenerating code; min_size=k+1). Under sustained mixed client
    load, m=3 OSDs die mid-window. Degraded reads must keep succeeding
    bit-identically the whole time (writes drop below min_size and
    stall — counted, not errors). The three revive with their stores;
    the stage reports time-to-clean, recovery MB/s (from the
    recovery_bytes_pushed counters), and client p99 during backfill.

    Phase B (single-shard repair): one OSD dies, fresh objects are
    written degraded, the OSD revives, and log-driven recovery rebuilds
    its shards through the CLAY sub-chunk repair plan — the
    repair-bytes ratio vs the full-stripe baseline (d/q helper
    fragments vs k whole chunks: 10/3 vs 8 chunks, ~0.42) is THE
    regenerating-code acceptance number, wired into the trend guard.
    """
    import asyncio

    KS, MS, DS = 8, 3, 10
    N_OSDS = KS + MS
    results: dict = {}

    async def wait_clean(osds, pool_name, timeout=90.0):
        from ceph_tpu.crush.crush import CRUSH_NONE
        loop = asyncio.get_running_loop()
        deadline = loop.time() + timeout
        while True:
            clean = True
            for osd in osds:
                for pg in osd.pgs.values():
                    if pg.pool.name != pool_name:
                        continue
                    if len(pg.acting) != N_OSDS or \
                            CRUSH_NONE in pg.acting:
                        clean = False
                    elif pg.is_primary():
                        if pg.state != "active" or pg._pending_recovery:
                            clean = False
                    elif pg.state not in ("active", "replica"):
                        clean = False
            # every PG must be hosted: primaries cover all of pg_num
            prim = {(pg.pgid.pool, pg.pgid.ps)
                    for osd in osds for pg in osd.pgs.values()
                    if pg.pool.name == pool_name and pg.is_primary()
                    and pg.state == "active"}
            if clean and len(prim) == 8:
                return loop.time()
            if loop.time() > deadline:
                return None
            await asyncio.sleep(0.25)

    async def wait_down(osds, dead, timeout=30.0):
        loop = asyncio.get_running_loop()
        deadline = loop.time() + timeout
        while loop.time() < deadline:
            maps = [o.osdmap for o in osds if o.whoami not in dead]
            if maps and all(
                    all(i in m.osds and not m.osds[i].up for i in dead)
                    for m in maps):
                return True
            await asyncio.sleep(0.2)
        return False

    def pattern(oid: str, gen: int, size: int) -> bytes:
        import hashlib
        seed = hashlib.sha256(f"{oid}:{gen}".encode()).digest()
        return (seed * (size // len(seed) + 1))[:size]

    def repair_totals(osds):
        fetched = full = 0
        for osd in osds:
            for pg in osd.pgs.values():
                b = pg.backend
                fetched += getattr(b, "repair_bytes_fetched", 0)
                full += getattr(b, "repair_bytes_full", 0)
        return fetched, full

    def pushed_total(osds):
        return sum(o.perf.dump().get("recovery_bytes_pushed", 0)
                   for o in osds)

    async def body():
        from ceph_tpu.objectstore.memstore import MemStore
        from ceph_tpu.osd.daemon import OSD
        from ceph_tpu.tools.cluster_boot import ephemeral_cluster

        stores: dict[int, MemStore] = {}

        def store_factory(tmp, i):
            stores[i] = MemStore(f"osd{i}")
            return stores[i]

        async with ephemeral_cluster(N_OSDS, prefix="bench-storm-",
                                     store_factory=store_factory) \
                as (client, osds, mon):
            mon_addrs = list(mon.monmap.mons.values())
            await client.command({
                "prefix": "osd erasure-code-profile set",
                "name": "stormprof",
                "profile": {"plugin": "clay", "k": str(KS),
                            "m": str(MS), "d": str(DS),
                            "scalar_mds": "jerasure"}})
            await client.pool_create("storm", pg_num=8,
                                     pool_type="erasure",
                                     erasure_code_profile="stormprof")
            io = client.ioctx("storm")
            pool = client.osdmap.get_pool("storm")
            obj = pool.stripe_width          # one full stripe per object
            results["failure_storm_object_bytes"] = obj

            # seed: immutable read-verified set + mutable churn set
            imm = {f"s{i:03d}": pattern(f"s{i:03d}", 0, obj)
                   for i in range(24)}
            for oid, data in imm.items():
                await io.write_full(oid, data)
            mut_gen = {f"w{i:02d}": 0 for i in range(8)}
            for oid in mut_gen:
                await io.write_full(oid, pattern(oid, 0, obj))

            import random as _random
            rng = _random.Random(42)
            lat: list[tuple[float, float, str]] = []
            stats = {"reads": 0, "writes": 0, "errors": 0, "stalls": 0,
                     "read_stalls": 0, "degraded_reads": 0}
            # oids with an outcome-unknown (timed-out) write: RADOS
            # semantics let the abandoned op land later, so their final
            # content is "any written generation", never garbage
            uncertain: set = set()
            stop_flag = [False]
            window = {"t_kill": None, "t_revive": None}
            loop = asyncio.get_running_loop()

            async def reader():
                oids = sorted(imm)
                while not stop_flag[0]:
                    oid = rng.choice(oids)
                    t0 = loop.time()
                    try:
                        got = await io.read(oid)
                    except Exception:
                        # a slow/timed-out read is degraded
                        # AVAILABILITY; only wrong bytes are a data
                        # error
                        stats["read_stalls"] += 1
                        continue
                    if got != imm[oid]:
                        stats["errors"] += 1
                        continue
                    now = loop.time()
                    lat.append((now, (now - t0) * 1e3, "read"))
                    stats["reads"] += 1
                    if window["t_kill"] is not None and \
                            window["t_revive"] is None:
                        stats["degraded_reads"] += 1
                    await asyncio.sleep(0.01)

            async def writer():
                oids = sorted(mut_gen)
                while not stop_flag[0]:
                    oid = rng.choice(oids)
                    gen = mut_gen[oid] + 1
                    t0 = loop.time()
                    try:
                        await client.submit(
                            "storm", oid,
                            [{"op": "write_full", "oid": oid}],
                            pattern(oid, gen, obj), timeout=4.0)
                        mut_gen[oid] = gen
                        now = loop.time()
                        lat.append((now, (now - t0) * 1e3, "write"))
                        stats["writes"] += 1
                    except Exception:
                        # below min_size the pool rejects writes: a
                        # stall with UNKNOWN outcome, not a data error
                        stats["stalls"] += 1
                        uncertain.add(oid)
                    await asyncio.sleep(0.02)

            load = [loop.create_task(reader()) for _ in range(3)] + \
                   [loop.create_task(writer()) for _ in range(2)]
            try:
                await asyncio.sleep(2.0)            # baseline window
                dead = [N_OSDS - 3, N_OSDS - 2, N_OSDS - 1]
                window["t_kill"] = loop.time()
                for i in dead:
                    await osds[i].stop()
                down_ok = await wait_down(osds, dead)
                results["failure_storm_marked_down"] = down_ok
                await asyncio.sleep(4.0)            # degraded window
                pushed0 = pushed_total(
                    [o for o in osds if o.whoami not in dead])
                window["t_revive"] = loop.time()
                for i in dead:
                    osd = OSD(i, mon_addrs, store=stores[i])
                    await osd.start()
                    osds[i] = osd
                t_clean = await wait_clean(osds, "storm")
                t_rec = (t_clean - window["t_revive"]) if t_clean \
                    else None
                await asyncio.sleep(0.5)
            finally:
                stop_flag[0] = True
                for t in load:
                    t.cancel()
                await asyncio.gather(*load, return_exceptions=True)

            pushed = pushed_total(osds) - pushed0
            results["failure_storm_reached_clean"] = t_rec is not None
            if t_rec is not None:
                # only recorded when clean was reached: the trend guard
                # skips missing keys, and a sentinel like -1.0 would
                # read as an improvement on a COST key exactly when the
                # cluster stopped converging
                results["failure_storm_time_to_clean_s"] = round(
                    t_rec, 2)
            # phase A recovery volume is whatever client writes landed
            # before the kill (informational: writes stall below
            # min_size, so the storm itself adds little to repair);
            # the guarded recovery-rate metric comes from phase B's
            # deterministic degraded-write workload
            results["failure_storm_storm_recovery_bytes"] = pushed
            backfill = [ms for t, ms, _ in lat
                        if window["t_revive"] is not None
                        and t >= window["t_revive"]]
            backfill.sort()
            results["failure_storm_backfill_p99_ms"] = round(
                backfill[int(0.99 * (len(backfill) - 1))], 1) \
                if backfill else 0.0
            degraded = [ms for t, ms, k in lat
                        if k == "read" and window["t_kill"] is not None
                        and window["t_kill"] <= t <
                        (window["t_revive"] or 1e18)]
            degraded.sort()
            results["failure_storm_degraded_p99_ms"] = round(
                degraded[int(0.99 * (len(degraded) - 1))], 1) \
                if degraded else 0.0
            results["failure_storm_degraded_reads"] = \
                stats["degraded_reads"]
            results["failure_storm_write_stalls"] = stats["stalls"]

            # time-resolved storm curve: per-second write MB/s and
            # client p99 across baseline -> kill -> degraded ->
            # backfill. The BENCH line carries the whole series (the
            # curve a flight-recorder timeline is read against); the
            # trend guard watches its p99 area, which a latency
            # regression ANYWHERE in the storm inflates even when the
            # end-state numbers recover
            if lat:
                t0x = lat[0][0]
                per_sec: dict[int, list] = {}
                for t, ms, kind in lat:
                    per_sec.setdefault(int(t - t0x), []).append((ms, kind))
                timeline = []
                for sec in sorted(per_sec):
                    sam = per_sec[sec]
                    mss = sorted(ms for ms, _ in sam)
                    writes = sum(1 for _, k in sam if k == "write")
                    timeline.append(
                        {"t": sec,
                         "write_mb_s": round(writes * obj / 1e6, 3),
                         "p99_ms": round(
                             mss[int(0.99 * (len(mss) - 1))], 2),
                         "reads": len(sam) - writes,
                         "writes": writes})
                results["failure_storm_timeline"] = timeline
                results["failure_storm_p99_area_ms_s"] = round(
                    sum(p["p99_ms"] for p in timeline), 1)
                if window["t_kill"] is not None:
                    results["failure_storm_kill_at_s"] = round(
                        window["t_kill"] - t0x, 2)
                if window["t_revive"] is not None:
                    results["failure_storm_revive_at_s"] = round(
                        window["t_revive"] - t0x, 2)

            # final verification: every object byte-identical to A
            # written generation — an uncertain (timed-out) write may
            # have landed late, but the bytes must never be garbage
            errors = stats["errors"]
            for oid, data in imm.items():
                if await io.read(oid) != data:
                    errors += 1
            for oid, gen in mut_gen.items():
                got = await io.read(oid)
                accept = range(gen + 3) if oid in uncertain \
                    else (gen, gen + 1)
                if not any(got == pattern(oid, g, obj) for g in accept):
                    errors += 1
            results["failure_storm_client_errors"] = errors
            results["failure_storm_read_stalls"] = stats["read_stalls"]
            log(f"failure_storm: clean={t_rec and round(t_rec, 1)}s "
                f"degraded_reads={stats['degraded_reads']} "
                f"errors={errors}")

            # -- phase B: single-shard repair-bytes ratio + recovery
            # rate over a DETERMINISTIC degraded-write workload.
            # Baselines exclude osd.0: it is about to be REPLACED by a
            # fresh instance whose counters start at zero, so including
            # its phase-A accumulation in f0 would subtract bytes that
            # no longer exist in f1 (skewing the ratio, possibly
            # negative) ------------------------------------------------
            f0, full0 = repair_totals(osds[1:])
            window["t_kill"] = window["t_revive"] = None
            await osds[0].stop()
            await wait_down(osds, [0])
            for i in range(16):
                oid = f"b{i:03d}"
                await io.write_full(oid, pattern(oid, 0, obj))
            pushed_b0 = pushed_total(osds[1:])
            osd = OSD(0, mon_addrs, store=stores[0])
            await osd.start()
            osds[0] = osd
            t_revive_b = loop.time()
            t_clean_b = await wait_clean(osds, "storm")
            pushed_b = pushed_total(osds) - pushed_b0
            rec_s = (t_clean_b - t_revive_b) if t_clean_b else None
            results["failure_storm_recovery_mb_s"] = round(
                pushed_b / rec_s / 1e6, 3) if rec_s else 0.0
            results["failure_storm_recovery_bytes"] = pushed_b
            f1, full1 = repair_totals(osds)
            fetched_b, full_b = f1 - f0, full1 - full0
            ratio = round(fetched_b / full_b, 4) if full_b else 1.0
            results["failure_storm_repair_ratio"] = ratio
            results["failure_storm_repair_fetched_mb"] = round(
                fetched_b / 1e6, 3)
            results["failure_storm_repair_full_equiv_mb"] = round(
                full_b / 1e6, 3)
            results["failure_storm_repair_clean"] = t_clean_b is not None
            for i in range(16):
                oid = f"b{i:03d}"
                if await io.read(oid) != pattern(oid, 0, obj):
                    results["failure_storm_client_errors"] += 1
            log(f"failure_storm: repair ratio {ratio} "
                f"({fetched_b} of {full_b} full-gather bytes)")

    asyncio.run(asyncio.wait_for(body(), 280))

    # -- phase C: flight-recorder drill — 3 OSDs killed AS A PROCESS.
    # A 6-OSD cluster over 2 worker processes (parent keeps mon +
    # client), worker shard1 (osds 0/2/4) SIGKILLed via the control
    # channel, a device fault armed on a survivor so the offload
    # breaker trips in worker shard2, then respawn and recover. The
    # merged `timeline dump` must tell the story in causal order
    # across >= 2 OS processes: injection -> mark-downs -> breaker
    # trip -> recovery-complete (OSD_DOWN health clear).
    async def drill():
        from ceph_tpu.mgr.daemon import MgrDaemon
        from ceph_tpu.tools.cluster_boot import ephemeral_cluster
        from ceph_tpu.utils import flight

        flight.reset()              # focus the ring on this drill
        loop = asyncio.get_running_loop()

        async def wait_flight(etype, entity_sub="", timeout=60.0):
            deadline = loop.time() + timeout
            while loop.time() < deadline:
                for e in flight.dump(etype)["events"]:
                    if entity_sub in e["entity"]:
                        return True
                await asyncio.sleep(0.25)
            return False

        async with ephemeral_cluster(6, prefix="bench-drill-",
                                     reactor_procs=2) \
                as (client, osds, mon):
            mon_addrs = list(mon.monmap.mons.values())
            mgr = MgrDaemon(mon_addrs, modules=[], exporter_port=None)
            await mgr.start()
            try:
                await client.command({
                    "prefix": "osd erasure-code-profile set",
                    "name": "drillprof",
                    "profile": {"plugin": "tpu", "k": "2", "m": "1"}})
                await client.pool_create(
                    "drill", pg_num=4, pool_type="erasure",
                    erasure_code_profile="drillprof")
                io = client.ioctx("drill")
                obj = client.osdmap.get_pool("drill").stripe_width
                for i in range(6):
                    await io.write_full(f"d{i:02d}", bytes([i]) * obj)

                # kill worker shard1 = osds 0/2/4 (place = 1 + seq%2)
                pool_h = osds[0].pool
                dead = [0, 2, 4]
                await pool_h.inject_crash(1)
                deadline = loop.time() + 40.0
                down_ok = False
                while loop.time() < deadline and not down_ok:
                    m = mon.osdmon.osdmap
                    down_ok = all(i in m.osds and not m.osds[i].up
                                  for i in dead)
                    await asyncio.sleep(0.25)
                results["failure_storm_drill_marked_down"] = down_ok

                # breaker trip in the SURVIVING worker: threshold 1 +
                # armed device fault, then degraded writes until the
                # trip shows in shard2's ring
                surv = osds[1]                      # shard 2
                await surv.config_set(
                    "ec_offload_breaker_threshold", 1)
                await surv.admin({"prefix": "inject", "what": "device",
                                  "count": 2, "whoami": surv.whoami})
                tripped = False
                for i in range(40):
                    try:
                        await client.submit(
                            "drill", f"w{i:02d}",
                            [{"op": "write_full", "oid": f"w{i:02d}"}],
                            bytes([i]) * obj, timeout=4.0)
                    except Exception:
                        pass                # peering/remap in progress
                    try:
                        ring = await surv.admin(
                            {"prefix": "events dump",
                             "type": "breaker_trip"}, timeout=5.0)
                        tripped = bool(ring["events"])
                    except Exception:
                        tripped = False
                    if tripped:
                        break
                    await asyncio.sleep(0.25)
                results["failure_storm_drill_breaker_tripped"] = tripped

                # respawn the dead worker; recovery-complete = the
                # mon's OSD_DOWN health check clearing (a flight event
                # in the parent ring)
                await pool_h.respawn(1)
                recovered = await wait_flight("health_clear",
                                              "OSD_DOWN", timeout=60.0)
                results["failure_storm_drill_recovered"] = recovered

                # merge: every worker's ring over the control channel +
                # the parent ring + whatever the mgr's report fan-in
                # already collected (dedup by (boot, seq) makes the
                # overlap harmless)
                extra = []
                for ref in (osds[0], osds[1]):
                    try:
                        extra.append(await ref.admin("events dump",
                                                     timeout=5.0))
                    except Exception:
                        pass
                tl = mgr.timeline_dump(extra_rings=extra)
                ev = tl["events"]

                def first(etype, sub=""):
                    for i, e in enumerate(ev):
                        if e["type"] == etype and sub in e["entity"]:
                            return i
                    return None
                i_inj = first("inject_crash")
                i_down = first("osd_markdown")
                i_trip = first("breaker_trip")
                i_rec = first("health_clear", "OSD_DOWN")
                order = [i_inj, i_down, i_trip, i_rec]
                results["failure_storm_drill_causal_ok"] = (
                    None not in order and order == sorted(order))
                results["failure_storm_drill_events"] = len(ev)
                results["failure_storm_drill_processes"] = len(
                    tl["processes"])
                log(f"failure_storm drill: events={len(ev)} "
                    f"processes={tl['processes']} "
                    f"order={order} causal_ok="
                    f"{results['failure_storm_drill_causal_ok']}")
            finally:
                await mgr.stop()

    try:
        asyncio.run(asyncio.wait_for(drill(), 170))
    except Exception as e:
        # the drill is an observability demonstration: a flaky respawn
        # or health wait must not discard phase A/B's guarded numbers
        results["failure_storm_drill_error"] = \
            f"{type(e).__name__}: {e}"
        log(f"failure_storm drill failed: {type(e).__name__}: {e}")

    # -- phase D: asynclockdep drill — two primaries cross their scrub
    # reservations (each holds its own osd_max_scrubs slot while
    # reserving the other's). The in-process watchdog must see the
    # wait-for cycle while it is LIVE, the mgr must raise
    # DEADLOCK_SUSPECTED from the shipped wait annotations and clear it
    # once the reservation-timeout abort breaks the cross, and a replay
    # must reproduce a bit-identical witness digest. Lockdep's client
    # cost is A/B'd on the same write workload (trend-guarded <5%).
    async def deadlock_drill():
        from ceph_tpu.mgr.daemon import MgrDaemon
        from ceph_tpu.tools.cluster_boot import ephemeral_cluster
        from ceph_tpu.utils import sanitizer

        loop = asyncio.get_running_loop()
        ring = {"osd.0:scrub_reservations", "osd.1:scrub_reservations"}

        def scrub_pgs(osds):
            out = {}
            for who in (0, 1):
                for pg in osds[who].pgs.values():
                    if pg.pool.name == "dl" and pg.is_primary() \
                            and pg.acting_peers():
                        out[who] = pg
                        break
            return out[0], out[1]

        async def crossed_round(osds, mgr):
            """One crossed-reservation deadlock: returns (in-process
            detect latency, observed witness digest, suspected-at-mgr
            flag, both rounds' results)."""
            pg0, pg1 = scrub_pgs(osds)
            t0 = loop.time()
            s0 = asyncio.ensure_future(pg0.scrub())
            s1 = asyncio.ensure_future(pg1.scrub())
            detect = digest = None
            suspected = False
            while loop.time() - t0 < 12.0 and not (detect and suspected):
                if detect is None:
                    scan = sanitizer.deadlock_scan(stuck_s=0.0)
                    for cyc in scan["cycles"]:
                        if set(cyc["resources"]) == ring:
                            detect = loop.time() - t0
                            digest = cyc["digest"]
                if not suspected:
                    try:
                        suspected = "DEADLOCK_SUSPECTED" in \
                            mgr._build_digest()["checks"] \
                            and mgr.deadlock_status()["suspected"]
                    except Exception:
                        suspected = False
                await asyncio.sleep(0.05)
            r0, r1 = await asyncio.gather(s0, s1)
            return detect, digest, suspected, r0, r1

        async with ephemeral_cluster(2, prefix="bench-dl-") \
                as (client, osds, mon):
            mgr = MgrDaemon(list(mon.monmap.mons.values()),
                            modules=[], exporter_port=None)
            await mgr.start()
            try:
                await client.pool_create("dl", pg_num=8, size=2)
                io = client.ioctx("dl")
                for i in range(8):
                    await io.write_full(f"d{i}", b"x" * 4096)

                async def client_burst(n=150, size=64 * 1024):
                    blob = b"y" * size
                    t = time.perf_counter()
                    for i in range(n):
                        await io.write_full(f"w{i % 32:02d}", blob)
                    return time.perf_counter() - t

                await client_burst(n=30)            # warm the path
                t_off = await client_burst()        # lockdep disarmed
                for o in osds:                      # arm via the knob
                    o.config.set("sanitizer_stuck_wait_s", 0.4)
                    o.config.set("sanitizer_lockdep", True)
                t_on = await client_burst()
                results["lockdep_overhead_pct"] = round(
                    (t_on - t_off) / t_off * 100.0, 2)

                # osd.0's shorter timeout makes it the deadlock breaker
                osds[0].config.set("osd_scrub_reserve_timeout", 3.0)
                osds[1].config.set("osd_scrub_reserve_timeout", 9.0)
                detect, digest, suspected, r0, r1 = \
                    await crossed_round(osds, mgr)
                results["deadlock_drill_detect_s"] = \
                    round(detect, 3) if detect is not None else None
                results["deadlock_drill_detected"] = (
                    detect is not None and detect < 2.0)
                results["deadlock_drill_witness_digest"] = digest
                results["deadlock_drill_suspected_raised"] = suspected
                # the abort path broke the cross: the breaker bailed,
                # the survivor's round ran to completion
                results["deadlock_drill_broken"] = (
                    bool(r0.get("reserve_failed"))
                    and not r1.get("reserve_failed")
                    and r1.get("errors") == 0)
                # ...and the health check clears once fresh reports
                # carry no annotations
                cleared = False
                deadline = loop.time() + 10.0
                while loop.time() < deadline and not cleared:
                    try:
                        cleared = "DEADLOCK_SUSPECTED" not in \
                            mgr._build_digest()["checks"]
                    except Exception:
                        cleared = False
                    await asyncio.sleep(0.25)
                results["deadlock_drill_suspected_cleared"] = cleared

                # replay: the witness digest fingerprints the resource
                # ring, not schedules or task names — a second crossed
                # round must reproduce it bit for bit
                detect2, digest2, _, _, _ = await crossed_round(osds,
                                                                mgr)
                results["deadlock_drill_replay_identical"] = (
                    digest is not None and digest == digest2)
                log(f"deadlock_drill: detect={detect and round(detect, 3)}s "
                    f"suspected={suspected} cleared={cleared} "
                    f"replay_ok={digest == digest2} "
                    f"lockdep_overhead={results['lockdep_overhead_pct']}%")
            finally:
                for o in osds:
                    try:
                        o.config.set("sanitizer_lockdep", False)
                    except Exception:
                        pass
                await mgr.stop()

    try:
        asyncio.run(asyncio.wait_for(deadlock_drill(), 140))
    except Exception as e:
        results["deadlock_drill_error"] = f"{type(e).__name__}: {e}"
        log(f"deadlock_drill failed: {type(e).__name__}: {e}")
    return results


# -- swarm: many-client fairness + per-client SLO observability ---------------

def stage_swarm() -> dict:
    """The multi-tenant lens, end to end on a live cluster (ROADMAP
    production-traffic item): >= 200 concurrent librados clients (mixed
    op sizes, zipfian hot keys, an injected slow-reader band) against
    an EC pool, with per-client SLO accounting armed on every OSD.
    Reports aggregate MB/s, the per-client p99 spread, and the
    fairness ratio max/median client p99 — the number an mClock-style
    QoS scheduler will be graded on — then verifies the observability
    pipeline under load: `ceph_client_*` families in a live exporter
    scrape, and the SLO_VIOLATIONS health check firing (and muting)
    under the slow-reader overload."""
    import asyncio
    import re as _re

    t0 = time.perf_counter()
    results: dict = {}
    N_CLIENTS, SECONDS, N_OSDS = 200, 6.0, 4
    SLO_READ_MS, SLO_WRITE_MS = 250.0, 500.0

    async def _http_get(addr, path: str) -> str:
        reader, writer = await asyncio.open_connection(*addr)
        writer.write(f"GET {path} HTTP/1.0\r\n\r\n".encode())
        await writer.drain()
        blob = await reader.read()
        writer.close()
        return blob.split(b"\r\n\r\n", 1)[1].decode()

    async def _poll_health(client, want_check: str, present: bool,
                           timeout: float = 25.0) -> dict:
        loop = asyncio.get_running_loop()
        deadline = loop.time() + timeout
        h: dict = {}
        while loop.time() < deadline:
            h = await client.command({"prefix": "health"})
            if (want_check in h.get("checks", {})) == present:
                return h
            await asyncio.sleep(0.5)
        return h

    async def body():
        import tempfile

        from ceph_tpu.tools.rados_swarm import raise_fd_limit, run_swarm
        from ceph_tpu.tools.vstart import VCluster

        raise_fd_limit()
        with tempfile.TemporaryDirectory(prefix="bench-swarm-") as base:
            c = VCluster(base, n_mons=1, n_osds=N_OSDS, with_mgr=True)
            try:
                await c.start()
                cl = await c.client()
                await cl.command({
                    "prefix": "osd erasure-code-profile set",
                    "name": "swarmprof",
                    "profile": {"plugin": "jerasure", "k": "2",
                                "m": "1"}})
                await cl.pool_create("swarm", pg_num=8,
                                     pool_type="erasure",
                                     erasure_code_profile="swarmprof")
                # arm the SLO engine hot on every OSD (the observer
                # pushes straight into the live ClientTable)
                for osd in c.osds.values():
                    osd.config.set("slo_read_ms", SLO_READ_MS)
                    osd.config.set("slo_write_ms", SLO_WRITE_MS)
                out = await run_swarm(
                    c.mon_addrs, "swarm", clients=N_CLIENTS,
                    seconds=SECONDS, objects=128, slow_readers=16,
                    connect_batch=40)
                out.pop("per_client", None)
                results["swarm_clients"] = out["clients"]
                results["swarm_mb_s"] = out["mb_s"]
                results["swarm_read_mb_s"] = out["read_mb_s"]
                results["swarm_write_mb_s"] = out["write_mb_s"]
                results["swarm_iops"] = out["iops"]
                results["swarm_errors"] = out["errors"]
                results["swarm_connect_s"] = out["connect_s"]
                results["swarm_client_p99_median_ms"] = \
                    out["median_p99_ms"]
                results["swarm_client_p99_max_ms"] = out["max_p99_ms"]
                results["swarm_p99_fairness"] = out["p99_fairness"]
                log(f"swarm: {out['clients']} clients {out['mb_s']} "
                    f"MB/s p99 med/max {out['median_p99_ms']}/"
                    f"{out['max_p99_ms']}ms fairness "
                    f"{out['p99_fairness']} errors={out['errors']}")

                # per-client accounting really landed on the OSDs
                tables = [o.optracker.clients.dump_clients(limit=1)
                          for o in c.osds.values()]
                results["swarm_osd_clients_tracked"] = sum(
                    t["num_clients"] for t in tables)

                # SLO_VIOLATIONS must FIRE under the overload...
                h = await _poll_health(cl, "SLO_VIOLATIONS", True)
                results["swarm_slo_fired"] = \
                    "SLO_VIOLATIONS" in h.get("checks", {})
                # ...the exporter must carry ceph_client_* families...
                text = await _http_get(c.mgr.exporter.addr, "/metrics")
                fams = sorted(set(_re.findall(
                    r"# TYPE (ceph_client_[a-z0-9_]+)", text)))
                series = sorted(set(_re.findall(
                    r'ceph_client="([^"]+)"', text)))
                results["swarm_client_families"] = len(fams)
                results["swarm_client_series"] = len(series)
                results["swarm_client_series_capped"] = \
                    len(series) <= 64
                log(f"swarm: exporter {len(fams)} ceph_client_* "
                    f"families, {len(series)} client series "
                    f"(fired={results['swarm_slo_fired']})")
                # ...and the check must MUTE on request
                await cl.command({"prefix": "health mute",
                                  "code": "SLO_VIOLATIONS", "ttl": 120})
                h = await _poll_health(cl, "SLO_VIOLATIONS", False,
                                       timeout=10.0)
                results["swarm_slo_muted"] = (
                    "SLO_VIOLATIONS" not in h.get("checks", {})
                    and "SLO_VIOLATIONS" in h.get("muted", {}))
                log(f"swarm: SLO_VIOLATIONS muted="
                    f"{results['swarm_slo_muted']}")
                # time-resolved leg: the mgr's metrics history sampled
                # the whole storm through the MMgrReport fan-in — emit
                # each OSD's per-second op-rate curve (the SHAPE the
                # QoS work will be graded on) plus the windowed p99
                # the history math recomputes from the bucket deltas
                hist = c.mgr.daemon_index.history
                curves = {}
                for daemon, samples in hist.series("op").items():
                    curves[daemon] = [
                        round((b - a) / max(tb - ta, 1e-9), 1)
                        for (ta, a), (tb, b) in zip(samples,
                                                    samples[1:])][-12:]
                results["swarm_op_rate_series"] = curves
                q = hist.query("op_total_us", window_s=SECONDS + 30)
                results["swarm_history_p99_ms"] = {
                    d: e.get("p99_ms")
                    for d, e in q["daemons"].items()}
                hst = hist.status()
                results["swarm_history_series"] = hst["series"]
                log(f"swarm: mgr history {hst['series']} series over "
                    f"{hst['daemons']} daemons, per-second op curves "
                    f"for {len(curves)} OSD(s)")
            finally:
                await c.stop()

    asyncio.run(asyncio.wait_for(body(), 280))
    results["elapsed_s"] = round(time.perf_counter() - t0, 1)
    return results


def stage_qos_storm() -> dict:
    """The dmclock QoS scheduler graded under a 1000-client storm with
    three adversarial tenants (hot-keyed bully, byte-heavy streamer,
    metadata-spammer) and a paced victim band, A/B against the legacy
    WRR path:

      0. polite-fleet baseline: the same paced majority + victim band
         with NO adversaries — the same-scale control that anchors the
         victim SLO and the fairness floor;
      A. scheduler OFF: the adversaries hog, the victim's p99 and the
         well-behaved fairness spread are the documented "worse" side;
      B. hot-toggle `osd_mclock_enabled` + per-tenant profiles (victim
         reservation, adversary limits) ON — same storm, plus an OSD
         kill/revive so RECOVERY must make progress through its
         reserved share while the storm rages;
      C. overload/shed: policy flipped to `shed` with a tight queue
         depth — adversary backlogs past the cap must be refused with
         MOSDOpThrottle (client-visible `throttled_ops`), every shed
         visible as a flight-recorder crumb and a per-tenant counter,
         and the admitted ops' p99 stays bounded.

    Also verifies the observability leg live: per-tenant `ceph_qos_*`
    families in an exporter scrape and nonzero mgr-side aggregation."""
    import asyncio
    import re as _re

    t0 = time.perf_counter()
    results: dict = {}
    N_CLIENTS, N_PROCS, SECONDS, N_OSDS = 1000, 3, 8.0, 4
    N_BULLY, N_STREAM, N_SPAM, N_VICTIM = 24, 24, 24, 64
    VICTIM_SLO_MS = 600.0
    # per-tenant profiles the ON phases run with: the victim band gets
    # a guaranteed reservation slice, the adversaries get hard limits
    # (cost-units/sec per OSD; a 4k op costs ~1.06 units). The
    # well-behaved majority is PACED (dmclock's evaluation shape:
    # constrained clients vs unconstrained hogs) — an unpaced majority
    # is its own DDoS and drowns the adversaries it is supposed to be
    # protected from. Limits are sized so polite demand + admitted
    # adversary throughput fits the box's measured service capacity:
    # dmclock arbitrates the queue, and a queue only forms around
    # capacity that exists.
    PROFILES = {"victim": {"reservation": 40.0, "weight": 4.0},
                "bully": {"limit": 4.0, "weight": 0.25},
                "streamer": {"limit": 4.0, "weight": 0.25},
                "spammer": {"limit": 6.0, "weight": 0.25}}

    async def _http_get(addr, path: str) -> str:
        reader, writer = await asyncio.open_connection(*addr)
        writer.write(f"GET {path} HTTP/1.0\r\n\r\n".encode())
        await writer.drain()
        blob = await reader.read()
        writer.close()
        return blob.split(b"\r\n\r\n", 1)[1].decode()

    async def body():
        import tempfile

        from ceph_tpu.tools.rados_swarm import raise_fd_limit, run_swarm
        from ceph_tpu.tools.vstart import VCluster
        from ceph_tpu.utils import flight

        raise_fd_limit(16384)
        storm_kw = dict(
            clients=N_CLIENTS, seconds=SECONDS, objects=128,
            slow_readers=0, bullies=N_BULLY, streamers=N_STREAM,
            spammers=N_SPAM, victims=N_VICTIM, victim_iops=0.5,
            normal_iops=0.1, adversary_depth=5, procs=N_PROCS,
            connect_batch=16, op_timeout=150.0, settle_s=3.0)
        with tempfile.TemporaryDirectory(prefix="bench-qos-") as base:
            c = VCluster(base, n_mons=1, n_osds=N_OSDS, with_mgr=True)
            try:
                await c.start()
                cl = await c.client()
                cl.OP_TIMEOUT = 60.0   # degraded writes ride peering
                # k=2,m=2 (size 4, min_size 3): one OSD down still
                # leaves min_size live shards, so the phase-B degraded
                # writes proceed instead of blocking on the interval
                await cl.command({
                    "prefix": "osd erasure-code-profile set",
                    "name": "swarmprof",
                    "profile": {"plugin": "jerasure", "k": "2",
                                "m": "2"}})
                await cl.pool_create("swarm", pg_num=8,
                                     pool_type="erasure",
                                     erasure_code_profile="swarmprof")

                # -- phase 0: polite-fleet baseline -------------------
                # the no-adversary control at the SAME connection
                # scale: the whole paced majority + victim band, no
                # hogs. Its victim p99 anchors the SLO and its
                # demand-fairness is the platform floor — the grades
                # below measure adversary-caused degradation, not the
                # absolute speed of whatever core-count this
                # container happens to have (a victims-only baseline
                # would hide the 1000-connection event-loop floor and
                # bill it to the adversaries).
                n_adv = N_BULLY + N_STREAM + N_SPAM
                pb = await run_swarm(
                    c.mon_addrs, "swarm",
                    **dict(storm_kw, clients=N_CLIENTS - n_adv,
                           bullies=0, streamers=0, spammers=0),
                    client_prefix="qz")
                slo_ms = max(VICTIM_SLO_MS,
                             2.0 * pb["victim_p99_ms"])
                results["qos_victim_baseline_p99_ms"] = \
                    pb["victim_p99_ms"]
                results["qos_baseline_fairness"] = \
                    pb["demand_fairness"]
                results["qos_baseline_errors"] = pb["errors"]
                log(f"qos baseline: {pb['clients']} polite clients "
                    f"fairness {pb['demand_fairness']} victim p99 "
                    f"{pb['victim_p99_ms']}ms -> SLO {slo_ms}ms")

                # -- phase A: scheduler OFF (legacy WRR) --------------
                off = await run_swarm(c.mon_addrs, "swarm",
                                      client_prefix="qa", **storm_kw)
                results["qos_storm_clients"] = off["clients"]
                results["qos_storm_procs"] = off["procs"]
                results["qos_errors_off"] = off["errors"]
                results["qos_fairness_ratio_off"] = \
                    off["demand_fairness"]
                results["qos_victim_isolation_off"] = \
                    off["victim_isolation"]
                results["qos_client_spread_off"] = off["good_fairness"]
                results["qos_victim_p99_off_ms"] = off["victim_p99_ms"]
                results["qos_victim_ops_off"] = \
                    off["per_tenant"].get("victim", {}).get("ops", 0)
                results["qos_goodput_off_mb_s"] = off["goodput_mb_s"]
                results["qos_mb_s_off"] = off["mb_s"]
                log(f"qos OFF: {off['clients']} clients fairness "
                    f"{off['demand_fairness']} victim p99 "
                    f"{off['victim_p99_ms']}ms goodput "
                    f"{off['goodput_mb_s']} MB/s errors={off['errors']}")

                # -- phase B: hot-toggle ON + recovery under storm ----
                for osd in c.osds.values():
                    osd.config.set("osd_mclock_tenant_profiles",
                                   json.dumps(PROFILES))
                    # recovery must CLEAR within the storm window, not
                    # trickle at the stock 4/s — client ops on a still-
                    # degraded object block on its recovery, so a slow
                    # reservation would punish exactly the tenants the
                    # scheduler protects
                    osd.config.set("osd_mclock_recovery_reservation",
                                   14.0)
                    osd.config.set("osd_mclock_enabled", True)
                # kill + degraded writes + revive: the revived OSD must
                # catch up THROUGH the scheduler's recovery reservation
                # while the storm runs. The degraded set is DEDICATED
                # `rec-*` objects no storm client touches: recovery of
                # an object gates client IO to it, and degrading storm
                # objects would measure recovery blocking, not
                # arbitration. 200 objects at ~12 pushes/s/OSD
                # (reservation 14, push cost ~1.2) keeps recovery
                # in flight across the whole storm window.
                victim_osd = N_OSDS - 1
                await c.kill_osd(victim_osd)
                io = cl.ioctx("swarm")
                for base in range(0, 200, 50):
                    await asyncio.gather(*[
                        io.write_full(f"rec-{r:04d}", bytes(16384))
                        for r in range(base, base + 50)])
                await c.start_osd(victim_osd)
                # let peering settle before the graded window opens —
                # ops parked on waiting_for_active measure peering,
                # not the arbitration under test (recovery itself
                # keeps running through the storm)
                await asyncio.sleep(5.0)
                on = await run_swarm(c.mon_addrs, "swarm",
                                     client_prefix="qb", **storm_kw)
                results["qos_errors_on"] = on["errors"]
                results["qos_fairness_ratio"] = on["demand_fairness"]
                results["qos_victim_isolation"] = \
                    on["victim_isolation"]
                results["qos_client_spread"] = on["good_fairness"]
                results["qos_victim_ops"] = \
                    on["per_tenant"].get("victim", {}).get("ops", 0)
                results["qos_victim_p99_ms"] = on["victim_p99_ms"]
                results["qos_goodput_mb_s"] = on["goodput_mb_s"]
                results["qos_mb_s_on"] = on["mb_s"]
                results["qos_victim_slo_ms"] = slo_ms
                results["qos_victim_slo_ok"] = bool(
                    0 < on["victim_p99_ms"] <= 4 * slo_ms)
                # graded bar: ON fairness within 1.5 absolute, or
                # within 1.5x of the no-adversary floor when the
                # platform itself cannot hold 1.5 at this scale
                results["qos_fairness_ok"] = bool(
                    on["demand_fairness"] <= max(
                        1.5, 1.5 * pb["demand_fairness"]))
                pushes = sum(
                    (o.perf.dump().get("recovery_push") or 0)
                    for o in c.osds.values())
                results["qos_recovery_pushes"] = pushes
                deferred = sum(o.op_queue.sched.total_deferred
                               for o in c.osds.values())
                results["qos_deferred_waits"] = deferred
                qs = c.osds[0].op_queue.qos_status()
                results["qos_status_entities"] = len(qs["entities"])
                results["qos_status_enabled"] = qs["enabled"]
                log(f"qos ON: fairness {on['demand_fairness']} victim "
                    f"p99 {on['victim_p99_ms']}ms goodput "
                    f"{on['goodput_mb_s']} MB/s recovery pushes "
                    f"{pushes} deferred {deferred} "
                    f"errors={on['errors']}")

                # -- phase C: overload admission control (shed) -------
                for osd in c.osds.values():
                    osd.config.set("osd_mclock_overload_policy", "shed")
                    osd.config.set("osd_mclock_shed_queue_depth", 8)
                shed_kw = dict(storm_kw, clients=300, procs=N_PROCS,
                               seconds=4.0, bullies=60, streamers=30,
                               spammers=60, victims=30)
                shed = await run_swarm(c.mon_addrs, "swarm",
                                       client_prefix="qc", **shed_kw)
                sheds = sum(o.op_queue.sched.total_shed
                            for o in c.osds.values())
                results["qos_shed_total"] = sheds
                results["qos_throttled_ops"] = shed["throttled_ops"]
                results["qos_shed_errors"] = shed["errors"]
                results["qos_admitted_p99_ms"] = shed["victim_p99_ms"]
                results["qos_shed_crumbs"] = len(
                    flight.dump(etype="qos_shed")["events"])
                results["qos_backpressure_crumbs"] = len(
                    flight.dump(etype="qos_backpressure")["events"])
                log(f"qos SHED: {sheds} shed, "
                    f"{shed['throttled_ops']} client-visible "
                    f"throttles, admitted victim p99 "
                    f"{shed['victim_p99_ms']}ms, "
                    f"{results['qos_shed_crumbs']} crumbs")

                # -- observability leg: mgr aggregation + exporter ----
                await asyncio.sleep(2.0)   # one report period
                agg = c.mgr.daemon_index.qos_aggregate()
                results["qos_mgr_tenants"] = len(agg)
                text = await _http_get(c.mgr.exporter.addr, "/metrics")
                fams = sorted(set(_re.findall(
                    r"# TYPE (ceph_qos_[a-z0-9_]+)", text)))
                series = sorted(set(_re.findall(
                    r'ceph_qos_[a-z0-9_]+\{tenant="([^"]+)"', text)))
                results["qos_exporter_families"] = len(fams)
                results["qos_tenant_series"] = len(series)
                log(f"qos obs: mgr {len(agg)} tenants, exporter "
                    f"{len(fams)} ceph_qos_* families over "
                    f"{len(series)} tenant series")
            finally:
                await c.stop()

    asyncio.run(asyncio.wait_for(body(), 520))
    results["elapsed_s"] = round(time.perf_counter() - t0, 1)
    return results


def stage_scrub_storm() -> dict:
    """Continuous integrity verification graded as a background
    workload on an 11-OSD CLAY(k=8,m=3,d=10, scalar_mds=tpu) pool:

      1. hash-path calibration: one clean deep-scrub round with host
         crc, one with the device CrcJob path (`ec_offload_crc_device`
         hot-flipped) — `scrub_mb_s` is the device round, the ratio is
         the device-vs-host grade, and the offload batch counters
         prove the digests really rode the CrcJob path;
      2. interference A/B: a paced swarm fleet measured with scrub
         OFF, then the same fleet with continuous deep-scrub rounds
         churning underneath — bit-rot injected on 12 objects via the
         faultinject hook right before the ON window, so detection
         latency (first scrub_mismatch flight crumb) and repair
         correctness (CLAY single-shard rebuild + read-back) are
         measured UNDER client load, and the victim p99 ratio is the
         interference grade (bar: <= 1.25x);
      3. health round-trip: fresh rot -> one deep round raises
         PG_DAMAGED + OSD_SCRUB_ERRORS through the report leg (and the
         exporter serves ceph_scrub_* families) -> a clean round
         retires the registry -> both checks clear."""
    import asyncio
    import re as _re

    t0 = time.perf_counter()
    results: dict = {}
    N_OSDS, K8, M3, D10 = 11, 8, 3, 10
    N_ROT, N_ROT2 = 12, 3
    N_CLIENTS, N_PROCS, SECONDS = 200, 2, 6.0

    async def _http_get(addr, path: str) -> str:
        reader, writer = await asyncio.open_connection(*addr)
        writer.write(f"GET {path} HTTP/1.0\r\n\r\n".encode())
        await writer.drain()
        blob = await reader.read()
        writer.close()
        return blob.split(b"\r\n\r\n", 1)[1].decode()

    async def body():
        import tempfile

        from ceph_tpu.offload import service as offload
        from ceph_tpu.osd import scrub as scrub_mod
        from ceph_tpu.tools.rados_swarm import raise_fd_limit, run_swarm
        from ceph_tpu.tools.vstart import VCluster
        from ceph_tpu.utils import flight

        raise_fd_limit(16384)
        storm_kw = dict(
            clients=N_CLIENTS, seconds=SECONDS, objects=96,
            slow_readers=0, bullies=0, streamers=0, spammers=0,
            victims=48, victim_iops=0.5, normal_iops=0.1,
            adversary_depth=1, procs=N_PROCS, connect_batch=16,
            op_timeout=60.0, settle_s=2.0)
        with tempfile.TemporaryDirectory(prefix="bench-scrub-") as base:
            c = VCluster(base, n_mons=1, n_osds=N_OSDS, with_mgr=True)
            try:
                await c.start()
                cl = await c.client()
                cl.OP_TIMEOUT = 60.0
                await cl.command({
                    "prefix": "osd erasure-code-profile set",
                    "name": "scrubprof",
                    "profile": {"plugin": "clay", "k": str(K8),
                                "m": str(M3), "d": str(D10),
                                "scalar_mds": "tpu"}})
                await cl.pool_create("swarm", pg_num=8,
                                     pool_type="erasure",
                                     erasure_code_profile="scrubprof")
                io = cl.ioctx("swarm")
                # the periodic scheduler must not fire mid-grade: every
                # round below is triggered explicitly. Small ranges +
                # an inter-range breather keep each write-gate hold
                # short — the gate covers one 4-object slice, and the
                # sleep (taken with the gate OPEN) lets queued client
                # writes drain between slices.
                for osd in c.osds.values():
                    osd.config.set("osd_scrub_interval", 100000.0)
                    osd.config.set("osd_scrub_chunk_max", 4)
                    osd.config.set("osd_scrub_sleep", 0.02)
                pool = cl.osdmap.get_pool("swarm")
                obj_bytes = 2 * pool.stripe_width
                payloads = {f"rot-{i:03d}": os.urandom(obj_bytes)
                            for i in range(128)}
                names = sorted(payloads)
                for i in range(0, len(names), 32):
                    await asyncio.gather(*[
                        io.write_full(n, payloads[n])
                        for n in names[i:i + 32]])
                results["scrub_storm_osds"] = N_OSDS
                results["scrub_storm_object_bytes"] = obj_bytes

                async def deep_round():
                    """One explicit deep round over every primary PG,
                    OSD by OSD (gates stagger instead of slamming every
                    PG at once); returns the cross-PG aggregate."""
                    rt = time.perf_counter()
                    agg = {"errors": 0, "repaired": 0, "bytes": 0,
                           "objects": 0}
                    for osd in c.osds.values():
                        for res in (await osd.scrub_all(
                                deep=True)).values():
                            if res:
                                agg["errors"] += res["errors"]
                                agg["repaired"] += res["repaired"]
                                agg["bytes"] += res["bytes_hashed"]
                                agg["objects"] += res["objects"]
                    agg["dt"] = time.perf_counter() - rt
                    return agg

                # -- phase 1: host vs device hashing calibration ------
                host = await deep_round()
                assert host["errors"] == 0, host
                host_mb_s = host["bytes"] / max(host["dt"], 1e-9) / 2**20
                results["scrub_hash_host_mb_s"] = round(host_mb_s, 2)
                # the guarded throughput number is the SHIPPING path —
                # host-native CrcJob batches through the offload
                # service (crc_device stays a measured experiment)
                results["scrub_mb_s"] = round(host_mb_s, 2)
                svc = offload.get_service()
                c.osds[0].config.set("ec_offload_crc_device", True)
                sperf = scrub_mod.scrub_perf()
                b_before = svc.stats["batches"]
                h_before = sperf.dump()["digest_batch_blocks"]["count"]
                dev = await deep_round()
                assert dev["errors"] == 0, dev
                dev_mb_s = dev["bytes"] / max(dev["dt"], 1e-9) / 2**20
                results["scrub_hash_device_mb_s"] = round(dev_mb_s, 2)
                results["scrub_device_vs_host_hash_ratio"] = round(
                    dev_mb_s / max(host_mb_s, 1e-9), 3)
                results["scrub_offload_batches"] = \
                    svc.stats["batches"] - b_before
                results["scrub_digest_batches"] = \
                    sperf.dump()["digest_batch_blocks"]["count"] \
                    - h_before
                # back to the host-native CrcJob dispatch for the
                # loaded phases: on this container's narrow H2D link
                # the device kernel is a measured loss (the ratio
                # above), and a slow hash stretches every write-gate
                # hold the interference grade is about to measure
                c.osds[0].config.set("ec_offload_crc_device", False)
                log(f"scrub hash: host {results['scrub_hash_host_mb_s']}"
                    f" MB/s, device {results['scrub_hash_device_mb_s']}"
                    f" MB/s over {results['scrub_offload_batches']} "
                    f"offload batches")

                # -- phase 2a: swarm baseline, scrub OFF --------------
                off = await run_swarm(c.mon_addrs, "swarm",
                                      client_prefix="so", **storm_kw)
                p99_off = off["victim_p99_ms"]
                results["scrub_client_p99_off_ms"] = p99_off
                results["scrub_baseline_errors"] = off["errors"]
                log(f"scrub OFF baseline: victim p99 {p99_off}ms "
                    f"errors={off['errors']}")

                # -- phase 2b: bit-rot + swarm with scrub churning ----
                rot = names[:N_ROT]
                osd_ids = sorted(c.osds)
                for i, oid in enumerate(rot):
                    r = await c.osds[osd_ids[i % N_OSDS]] \
                        ._inject_bitrot(oid)
                    assert r.get("injected") == "bitrot", r
                t_inject = time.monotonic()
                results["scrub_bitrot_injected"] = len(rot)

                churn = {"errors": 0, "repaired": 0, "bytes": 0,
                         "rounds": 0, "busy_s": 0.0}
                stop = asyncio.Event()
                prim_pgs = [pg for osd in c.osds.values()
                            for pg in osd.pgs.values()
                            if pg.is_primary() and pg.state == "active"]

                async def scrub_churn():
                    """Continuous verification shaped for live
                    clusters: ONE PG's deep round at a time with a
                    breather between — the whole-round write gate only
                    ever covers one PG, so a colliding client write
                    waits one short round, not a full sweep."""
                    i = 0
                    while not stop.is_set():
                        pg = prim_pgs[i % len(prim_pgs)]
                        i += 1
                        rt = time.perf_counter()
                        res = await pg.scrub(deep=True)
                        churn["busy_s"] += time.perf_counter() - rt
                        churn["errors"] += res["errors"]
                        churn["repaired"] += res["repaired"]
                        churn["bytes"] += res["bytes_hashed"]
                        churn["rounds"] += 1
                        try:
                            await asyncio.wait_for(stop.wait(), 0.25)
                        except asyncio.TimeoutError:
                            pass

                churn_task = asyncio.get_running_loop().create_task(
                    scrub_churn())
                try:
                    on = await run_swarm(c.mon_addrs, "swarm",
                                         client_prefix="sn", **storm_kw)
                finally:
                    stop.set()
                    await churn_task
                # sweep the stragglers: PGs whose turn never came in
                # the loaded window still owe their detection + repair
                sweep = await deep_round()
                churn["errors"] += sweep["errors"]
                churn["repaired"] += sweep["repaired"]
                p99_on = on["victim_p99_ms"]
                results["scrub_client_p99_on_ms"] = p99_on
                results["scrub_storm_client_errors"] = on["errors"]
                results["scrub_rounds_under_load"] = churn["rounds"]
                results["scrub_errors_found"] = churn["errors"]
                results["scrub_errors_repaired"] = churn["repaired"]
                results["scrub_under_load_mb_s"] = round(
                    churn["bytes"] / max(churn["busy_s"], 1e-9) / 2**20,
                    2)
                results["scrub_client_p99_interference_pct"] = round(
                    100.0 * p99_on / max(p99_off, 1e-9), 1)
                results["scrub_interference_ok"] = bool(
                    p99_on <= 1.25 * p99_off)
                mism = [e for e in
                        flight.dump(etype="scrub_mismatch")["events"]
                        if e["detail"].get("oid", "").startswith("rot-")]
                if mism:
                    results["scrub_detect_latency_s"] = round(
                        min(e["mono"] for e in mism) - t_inject, 3)
                results["scrub_repair_crumbs"] = len(
                    flight.dump(etype="scrub_repair")["events"])
                bad = 0
                for oid in rot:
                    if await io.read(oid) != payloads[oid]:
                        bad += 1
                results["scrub_repair_readback_bad"] = bad
                log(f"scrub ON: {churn['rounds']} rounds under load, "
                    f"{churn['errors']} found {churn['repaired']} "
                    f"repaired, detect "
                    f"{results.get('scrub_detect_latency_s')}s, victim "
                    f"p99 {p99_on}ms vs {p99_off}ms off "
                    f"({results['scrub_client_p99_interference_pct']}%)"
                    f" readback_bad={bad}")

                # -- phase 3: health raise -> exporter -> clear -------
                rot2 = names[N_ROT:N_ROT + N_ROT2]
                for i, oid in enumerate(rot2):
                    r = await c.osds[osd_ids[(i + 5) % N_OSDS]] \
                        ._inject_bitrot(oid)
                    assert r.get("injected") == "bitrot", r
                hr = await deep_round()
                assert hr["errors"] >= len(rot2), hr
                registry = sum(
                    o._list_inconsistent(None)["objects"]
                    for o in c.osds.values())
                results["scrub_registry_objects"] = registry

                async def health_has(*codes):
                    h = await cl.command({"prefix": "health detail"})
                    return all(code in h["checks"] for code in codes)

                deadline = asyncio.get_running_loop().time() + 30
                raised = False
                while asyncio.get_running_loop().time() < deadline:
                    if await health_has("PG_DAMAGED",
                                        "OSD_SCRUB_ERRORS"):
                        raised = True
                        break
                    await asyncio.sleep(0.5)
                results["scrub_health_raised"] = raised
                text = await _http_get(c.mgr.exporter.addr, "/metrics")
                fams = sorted(set(_re.findall(
                    r"# TYPE (ceph_scrub_[a-z0-9_]+)", text)))
                results["scrub_exporter_families"] = len(fams)
                clean = await deep_round()
                assert clean["errors"] == 0, clean
                deadline = asyncio.get_running_loop().time() + 30
                cleared = False
                while asyncio.get_running_loop().time() < deadline:
                    if not await health_has("PG_DAMAGED") \
                            and not await health_has(
                                "OSD_SCRUB_ERRORS"):
                        cleared = True
                        break
                    await asyncio.sleep(0.5)
                results["scrub_health_cleared"] = cleared
                log(f"scrub health: raised={raised} cleared={cleared} "
                    f"{len(fams)} ceph_scrub_* exporter families, "
                    f"registry held {registry} objects")
            finally:
                await c.stop()

    asyncio.run(asyncio.wait_for(body(), 520))
    results["elapsed_s"] = round(time.perf_counter() - t0, 1)
    return results


# -- attribution: the "where the 450x goes" waterfall -------------------------

#: waterfall buckets in pipeline order; "other" is the residual the
#: instruments cannot yet name (python messaging, scheduling) — the
#: number the sharded-OSD work exists to shrink
ATTRIBUTION_BUCKETS = ("queue_wait", "copy", "h2d", "kernel", "d2h",
                       "commit", "other")


def attribution_from_spans(spans: list[dict]) -> dict:
    """Decompose cluster EC write latency into the waterfall buckets
    from REAL span data (PR 1's tracer + this PR's copy/h2d/kernel/d2h
    span attributes). Aggregation is per-trace: only traces carrying an
    `osd_op` root contribute, `op_total` is shard-queue wait + osd_op
    execution wall (the osd_op span opens AFTER dequeue, so its
    queue_wait_us tag is time the span does not cover), and a trace's
    commit bucket is its SLOWEST store_commit (parallel shard
    commits gate the op on the max, not the sum). Shared offload
    batches land in one member trace's waterfall; aggregated over the
    run the totals amortize correctly. Returns per-op mean µs per
    bucket plus percentages; buckets (with the explicit `other`
    residual) sum to op_total by construction unless shared-batch
    overcounting pushes them past it — `attributed_fraction` records
    exactly how much of op_total the named buckets explain."""
    by_trace: dict[str, list[dict]] = {}
    for s in spans:
        by_trace.setdefault(s["trace_id"], []).append(s)
    n_ops = 0
    total_us = 0.0
    buckets = dict.fromkeys(ATTRIBUTION_BUCKETS, 0.0)
    for ss in by_trace.values():
        roots = [s for s in ss if s["name"] == "osd_op"]
        if not roots:
            continue                    # orphan batch/flush trace
        n_ops += len(roots)
        total_us += sum(
            s["duration_us"]
            + float((s.get("tags") or {}).get("queue_wait_us") or 0.0)
            for s in roots)
        for s in ss:
            tags = s.get("tags") or {}
            name = s["name"]
            if name == "osd_op":
                buckets["queue_wait"] += float(
                    tags.get("queue_wait_us") or 0.0)
            elif name == "offload_queue_wait":
                buckets["queue_wait"] += s["duration_us"]
            elif name in ("ec_encode", "ec_decode", "offload_batch"):
                buckets["copy"] += float(tags.get("copy_us") or 0.0) \
                    + float(tags.get("stack_us") or 0.0)
            # offload_batch carries the hops of the staged dispatch
            # (the service stamps them without serializing anything):
            # device_put returning, the kernel call returning, and the
            # result arriving, which is the kernel's end and the D2H
            if name == "offload_batch":
                buckets["h2d"] += float(tags.get("h2d_submit_us") or 0.0)
                buckets["kernel"] += float(tags.get("launch_us") or 0.0)
                buckets["d2h"] += float(tags.get("result_wait_us") or 0.0)
        commits = [s["duration_us"] for s in ss
                   if s["name"] == "store_commit"]
        if commits:
            buckets["commit"] += max(commits)
    known = sum(v for b, v in buckets.items() if b != "other")
    buckets["other"] = max(0.0, total_us - known)
    return {
        "ops": n_ops,
        "op_total_us": round(total_us / n_ops, 1) if n_ops else 0.0,
        "buckets_us": {b: round(v / n_ops, 1) if n_ops else 0.0
                       for b, v in buckets.items()},
        "bucket_pct": {b: round(100.0 * v / total_us, 1) if total_us
                       else 0.0 for b, v in buckets.items()},
        "attributed_fraction": round(known / total_us, 4) if total_us
        else 0.0,
    }


def stage_attribution(seconds: float = 2.0, ab_seconds: float = 1.5,
                      ab_reps: int = 3) -> dict:
    """The data-path attribution profiler, end to end on a live
    cluster: tracer + copy ledger + loop profiler armed around a timed
    EC write window (plugin=tpu), then the span stream decomposed into
    the queue-wait/copy/H2D/kernel/D2H/commit waterfall, with
    copy-amplification (bytes-copied / bytes-written) and per-device
    offload utilization riding the same record. This is the instrument
    the zero-copy and sharded-OSD roadmap items are graded against."""
    import asyncio

    t0 = time.perf_counter()
    import jax
    platform = jax.devices()[0].platform
    log(f"attribution: jax backend {platform} "
        f"({time.perf_counter() - t0:.1f}s init)")
    results: dict = {"attribution_platform": platform}
    KA, MA = 2, 1
    OBJ = KA * 4096
    SECONDS, CONC = seconds, 8

    async def body():
        from ceph_tpu import offload
        from ceph_tpu.tools.cluster_boot import ephemeral_cluster
        from ceph_tpu.tools.rados_bench import _phase
        from ceph_tpu.utils import copytrack, loopprof, tracer

        # profile the SHARDED runtime (capped by the bench knob): the
        # stage then reports loop_busy_fraction per reactor shard plus
        # the busy skew the trend guard watches
        n_shards = min(2, _reactor_shards_knob())
        async with ephemeral_cluster(KA + MA, prefix="bench-attr-",
                                     reactor_shards=n_shards) \
                as (client, osds, _mon):
            try:
                await client.command({
                    "prefix": "osd erasure-code-profile set",
                    "name": "attrprof",
                    "profile": {"plugin": "tpu", "k": str(KA),
                                "m": str(MA)}})
                await client.pool_create("attr", pg_num=4,
                                         pool_type="erasure",
                                         erasure_code_profile="attrprof")
                io = client.ioctx("attr")
                svc = offload.get_service()
                payload = bytes(OBJ)
                # warm: XLA compiles + sessions open outside the window
                await asyncio.gather(*[io.write_full(f"warm-{i}", payload)
                                       for i in range(4)])
                # arm every instrument, zeroed, for the measured window:
                # tracer.enable() arms the loop account on this loop and
                # on every reactor shard at its first span
                tracer.enable(max_spans=65536)
                tracer.reset()
                copytrack.reset()
                loopprof.reset()
                dev_base = svc.device_snapshot()
                counts: dict = {}
                t_win = time.perf_counter()
                w = await _phase(io, "write", CONC, SECONDS, OBJ, counts)
                await svc.drain()
                window_s = time.perf_counter() - t_win
                tracer.disable()
                prof = loopprof.dump()
                bytes_written = w["ops"] * OBJ
                att = attribution_from_spans(tracer.collector().spans())
                att["copy_amplification"] = \
                    copytrack.amplification(bytes_written)
                att["bytes_written"] = bytes_written
                snap = copytrack.snapshot()
                att["copy_ledger"] = {
                    s: {"copied_mb": round(d["copied_bytes"] / 1e6, 3),
                        "referenced_mb": round(
                            d["referenced_bytes"] / 1e6, 3)}
                    for s, d in snap["stages"].items()}
                att["loop_busy_fraction"] = prof["loop_busy_fraction"]
                # per-reactor-shard busy fractions + skew: the numbers
                # the sharded-OSD runtime is graded on ((max-min)/max;
                # a placement/affinity regression rises here first)
                att["reactor_shards"] = n_shards
                att["per_shard"] = prof.get("shards", {})
                att["shard_busy_skew"] = prof.get("shard_busy_skew", 0.0)
                results["shard_busy_skew"] = att["shard_busy_skew"]
                att["loop_labels_us"] = prof["labels_us"]
                att["per_device"] = {}
                for dev, d in svc.device_snapshot().items():
                    base = dev_base.get(dev, {})
                    busy = d["busy_s"] - base.get("busy_s", 0.0)
                    att["per_device"][dev] = {
                        "busy_fraction": round(busy / window_s, 4)
                        if window_s > 0 else 0.0,
                        "bytes": d["bytes"] - base.get("bytes", 0),
                        "batches": d["batches"] - base.get("batches", 0),
                        "ops": d["ops"] - base.get("ops", 0),
                    }
                # fan-out balance: busy-fraction skew across the
                # accelerator slots that saw traffic this window
                # ((max-min)/max; 0 = perfectly balanced, trend-guarded
                # so a routing regression shows up as a rise)
                active = [d["busy_fraction"]
                          for dev, d in att["per_device"].items()
                          if dev != "host" and d["busy_fraction"] > 0]
                att["device_busy_skew"] = round(
                    (max(active) - min(active)) / max(active), 4) \
                    if len(active) >= 2 else 0.0
                results["device_busy_skew"] = att["device_busy_skew"]
                bk = att["buckets_us"]
                # Python-per-op: what's left of op_total after the
                # device legs (h2d/kernel/d2h), the metered copies, and
                # the store commit — the messaging/dispatch/scheduling
                # Python this PR's batching + native frame path exists
                # to shrink (trend-guarded as a COST: a rise is a
                # regression even when MB/s holds)
                att["python_us_per_op"] = round(max(0.0, (
                    att["op_total_us"] - bk["h2d"] - bk["kernel"]
                    - bk["d2h"] - bk["copy"] - bk["commit"])), 1)
                results["python_us_per_op"] = att["python_us_per_op"]
                results["attribution"] = att
                results["copy_amplification"] = att["copy_amplification"]
                results["loop_busy_fraction"] = att["loop_busy_fraction"]
                results["attribution_write_mb_s"] = w["mb_per_s"]
                log(f"attribution: op_total {att['op_total_us']}us over "
                    f"{att['ops']} ops | " + " ".join(
                        f"{b}={bk[b]}" for b in ATTRIBUTION_BUCKETS)
                    + f" | copy_amp {att['copy_amplification']} "
                    f"loop_busy {att['loop_busy_fraction']} "
                    f"shards={att['per_shard']} "
                    f"skew={att['shard_busy_skew']}")
                # tracing-overhead A/B (tracing v2): off vs the
                # always-on production config (sample_rate=0.01 + tail
                # retention) vs full tracing, same cluster, same write
                # phase. Each mode window is SANDWICHED between off
                # windows and scored against their mean: the shared
                # cluster AGES monotonically across windows (pg log
                # windows fill, object count grows — the same handicap
                # the pipeline sweep dodges with fresh clusters), so any
                # schedule that compares windows far apart in time —
                # sequential blocks, even rotated round-robins — books
                # aging as tracer cost. Adjacent offs age ~equally and
                # the sandwich cancels linear drift in either direction;
                # a discarded warmup window absorbs first-window JIT /
                # allocator effects, and best-of-reps on the ratio
                # drops one-off stall windows (compaction, GC) that
                # would otherwise land on whichever mode drew them.
                # The guarded key is the production config.
                AB_SECONDS, AB_REPS = ab_seconds, ab_reps

                def _arm_off() -> None:
                    tracer.disable()
                    tracer.set_sampling(rate=0.0, tail_slow_ms=0.0)

                def _arm_sampled() -> None:
                    tracer.disable()
                    tracer.set_sampling(rate=0.01, tail_slow_ms=250.0)

                def _arm_full() -> None:
                    tracer.set_sampling(rate=0.0, tail_slow_ms=0.0)
                    tracer.enable(max_spans=65536)

                async def _ab_window() -> float:
                    tracer.reset()
                    r = await _phase(io, "write", CONC, AB_SECONDS,
                                     OBJ, {})
                    await svc.drain()
                    return r["mb_per_s"]

                ab_modes = [("sampled_tail", _arm_sampled),
                            ("full", _arm_full)]
                ab_ratio = {name: 0.0 for name, _ in ab_modes}
                ab_rate = {name: 0.0 for name, _ in ab_modes}
                ab_off = 0.0
                _arm_off()
                await _ab_window()          # warmup, discarded
                for _rep in range(AB_REPS):
                    # chain: off, sampled, off, full, off — each mode
                    # window scored vs the mean of its two neighbours
                    _arm_off()
                    off_prev = await _ab_window()
                    for name, arm in ab_modes:
                        arm()
                        rate = await _ab_window()
                        _arm_off()
                        off_next = await _ab_window()
                        base = (off_prev + off_next) / 2.0
                        ab_off = max(ab_off, base)
                        ab_rate[name] = max(ab_rate[name], rate)
                        if base > 0:
                            ab_ratio[name] = max(ab_ratio[name],
                                                 rate / base)
                        off_prev = off_next
                tracer.disable()
                tracer.reset()

                def _overhead(ratio: float) -> float:
                    return round(max(0.0, (1.0 - ratio) * 100.0), 2)
                results["tracing_ab_mb_s"] = {
                    "off": round(ab_off, 2),
                    "sampled_tail": round(ab_rate["sampled_tail"], 2),
                    "full": round(ab_rate["full"], 2)}
                results["tracing_overhead_pct"] = \
                    _overhead(ab_ratio["sampled_tail"])
                results["tracing_overhead_full_pct"] = \
                    _overhead(ab_ratio["full"])
                log(f"attribution: tracing A/B off={ab_off:.1f} "
                    f"sampled+tail={ab_rate['sampled_tail']:.1f} "
                    f"full={ab_rate['full']:.1f} MB/s -> overhead "
                    f"{results['tracing_overhead_pct']}% "
                    f"(full {results['tracing_overhead_full_pct']}%)")
            finally:
                tracer.disable()
                tracer.set_sampling(rate=0.0, tail_slow_ms=0.0)

    asyncio.run(asyncio.wait_for(body(), 150))
    results["elapsed_s"] = round(time.perf_counter() - t0, 1)
    return results


def stage_interleave() -> dict:
    """The interlock qa sweep as a bench stage: seed-swept schedule
    exploration over a pipelined EC cluster, run three ways — explorer
    only (flight recorder off), explorer + full sanitizer (generation
    guards, lockset recorder, debug mode), and explorer + the full
    observability plane (flight recorder on + a live mgr sampling
    metrics history from every daemon's reports) — so the JSON line
    carries seeds run, distinct schedules explored, and BOTH overheads
    the trend guard watches (a creeping guard or recorder cost would
    quietly price the qa tier out of CI)."""
    import asyncio

    t0 = time.perf_counter()
    SEEDS, N_OBJECTS, REPS = 12, 8, 2
    KI, MI = 2, 1
    OBJ = KI * 4096

    async def sweep(armed: bool,
                    recorder: bool = False) -> tuple[float, set, int]:
        from ceph_tpu.qa import interleave
        from ceph_tpu.tools.cluster_boot import ephemeral_cluster
        from ceph_tpu.utils import flight, sanitizer
        digests: set = set()
        decisions = 0
        async with ephemeral_cluster(KI + MI, prefix="bench-ilv-") \
                as (client, osds, _mon):
            await client.command({
                "prefix": "osd erasure-code-profile set",
                "name": "ilvprof",
                "profile": {"plugin": "jerasure", "k": str(KI),
                            "m": str(MI)}})
            await client.pool_create("ilv", pg_num=1,
                                     pool_type="erasure",
                                     erasure_code_profile="ilvprof")
            io = client.ioctx("ilv")
            for o in osds:
                o.config.set("osd_pg_pipeline_depth", 4)
            loop = asyncio.get_running_loop()
            # the recorder mode measures the WHOLE observability plane:
            # flight ring armed + a live mgr whose report fan-in feeds
            # the metrics-history sampler; the other modes run with the
            # ring off so the baseline stays un-instrumented
            flight.configure(enabled=recorder)
            mgr = None
            if recorder:
                from ceph_tpu.mgr.daemon import MgrDaemon
                mgr = MgrDaemon(list(_mon.monmap.mons.values()),
                                modules=[], exporter_port=None)
                await mgr.start()
            if armed:
                sanitizer.install(loop, slow_callback_s=5.0)
            try:
                # warm round outside the timed window
                await asyncio.gather(*[io.write_full(f"w{i}", bytes(OBJ))
                                       for i in range(4)])
                t1 = time.perf_counter()
                for seed in range(SEEDS):
                    async with interleave.explore(seed) as ex:
                        payloads = {
                            f"s{seed}-{i}":
                                bytes([32 + (seed * 7 + i) % 90]) * OBJ
                            for i in range(N_OBJECTS)}
                        await asyncio.gather(*[io.write_full(k, v)
                                               for k, v in
                                               payloads.items()])
                        for k, v in payloads.items():
                            assert await io.read(k) == v
                        digests.add(ex.digest())
                        decisions += ex.decisions
                elapsed = time.perf_counter() - t1
                if armed and sanitizer.lockset_conflicts():
                    raise AssertionError(
                        f"lockset conflicts under sweep: "
                        f"{sanitizer.lockset_conflicts()[:3]}")
            finally:
                if armed:
                    sanitizer.uninstall(loop)
                    sanitizer.clear_lockset_conflicts()
                if mgr is not None:
                    await mgr.stop()
                flight.configure(enabled=True)
        return elapsed, digests, decisions

    # alternate A/B/C and take per-mode minima: the 2-core container is
    # noisy, and min-of-reps is the steadier overhead estimator
    plain_s, armed_s, flight_s = [], [], []
    schedules: set = set()
    decisions = 0
    for _ in range(REPS):
        el, dg, dc = asyncio.run(asyncio.wait_for(sweep(False), 180))
        plain_s.append(el)
        schedules |= dg
        decisions += dc
        el, dg, dc = asyncio.run(asyncio.wait_for(sweep(True), 180))
        armed_s.append(el)
        schedules |= dg
        decisions += dc
        el, dg, dc = asyncio.run(asyncio.wait_for(
            sweep(False, recorder=True), 180))
        flight_s.append(el)
        schedules |= dg
        decisions += dc
    base, guarded, rec = min(plain_s), min(armed_s), min(flight_s)
    overhead = max(0.0, (guarded - base) / base * 100.0) if base else 0.0
    rec_overhead = max(0.0, (rec - base) / base * 100.0) if base else 0.0
    log(f"interleave: {SEEDS} seeds x {REPS} reps, "
        f"{len(schedules)} schedules, plain {base:.2f}s vs "
        f"sanitizer {guarded:.2f}s (+{overhead:.0f}%) vs "
        f"recorder+history {rec:.2f}s (+{rec_overhead:.0f}%)")
    return {
        "platform": "cpu",
        "interleave_seeds": SEEDS * REPS * 3,
        "interleave_schedules_explored": len(schedules),
        "interleave_decisions": decisions,
        "interleave_plain_s": round(base, 3),
        "interleave_sanitizer_s": round(guarded, 3),
        "interleave_sanitizer_overhead_pct": round(overhead, 1),
        "interleave_flight_s": round(rec, 3),
        "flight_history_overhead_pct": round(rec_overhead, 1),
        "elapsed_s": round(time.perf_counter() - t0, 1),
    }


# -- bench trend guard --------------------------------------------------------
# The r4->r5 device encode number slid 35.2 -> 31.96 GB/s and nothing
# noticed until a human diffed the JSON by hand (VERDICT weak #5). The
# guard compares each run's device codec numbers against the newest
# committed BENCH_r*.json and embeds the verdict in the output line, so
# a silent slide becomes a loud `regression_pct` the round it happens.

TREND_KEYS = ("tpu_encode", "tpu_decode", "failure_storm_recovery_mb_s",
              "scaling_efficiency", "cluster_ec_write_mb_s",
              "cluster_ec_tpu_write_mb_s_sharded",
              "cluster_ec_write_mb_s_procs", "swarm_mb_s",
              # storm goodput for the well-behaved tenants with the
              # QoS arbiter ON: a drop means isolation got leakier or
              # the arbiter started taxing the good citizens
              "qos_goodput_mb_s",
              # deep-scrub hashing throughput through the device CrcJob
              # path (the clean calibration round): a drop means the
              # digest batching or the offload crc leg got slower
              "scrub_mb_s",
              "offload_mean_batch_ops",
              # the r04->r05 35.2->32.0 GB/s slide, re-baselined as a
              # fraction of the measured device peak: normalizing by
              # the same-run peak keeps the guard meaningful across
              # container/backend drift that moves BOTH numbers
              "tpu_encode_roofline_pct")
#: keys where UP is the regression direction: more copied bytes per
#: written byte, a busier event loop, a slower recovery to clean, a
#: repair fetch creeping back toward the full-stripe baseline, the
#: mesh fan-out leaving devices idle, or the reactor shards going
#: lopsided is a slide even when the GB/s numbers hold. Guarded once
#: two rounds carry them (older rounds simply lack the keys).
TREND_KEYS_COST = ("copy_amplification", "loop_busy_fraction",
                   "failure_storm_time_to_clean_s",
                   "failure_storm_repair_ratio",
                   "device_busy_skew", "shard_busy_skew",
                   "shard_busy_skew_procs",
                   "swarm_p99_fairness", "python_us_per_op",
                   # scheduler-ON isolation figures: the well-behaved
                   # fairness spread widening or the paced victim
                   # band's p99 creeping up IS the QoS regression
                   "qos_fairness_ratio", "qos_victim_p99_ms",
                   # scrub-ON victim p99 as % of the scrub-OFF
                   # baseline under the same swarm load: creeping up
                   # means background verification started taxing
                   # foreground clients
                   "scrub_client_p99_interference_pct",
                   "msgr_frames_per_ec_write",
                   "pg_pipeline_stall_fraction",
                   "interleave_sanitizer_overhead_pct",
                   "flight_history_overhead_pct",
                   "failure_storm_p99_area_ms_s",
                   "tracing_overhead_pct",
                   # armed-vs-disarmed lockdep tax on the client write
                   # path (deadlock_drill A/B): must stay under ~5%
                   "lockdep_overhead_pct")
TREND_THRESHOLD_PCT = 10.0


def previous_bench(repo: str) -> tuple[str, str | None, dict] | None:
    """Newest committed round: (filename, platform, detail-metrics).

    BENCH_r*.json wraps the bench line under "parsed" (driver format);
    a bare bench.py line is accepted too. Unreadable/garbled files are
    skipped rather than failing the bench."""
    rounds: list[tuple[int, str]] = []
    for path in glob.glob(os.path.join(repo, "BENCH_r*.json")):
        m = re.search(r"BENCH_r(\d+)\.json$", path)
        if m:
            rounds.append((int(m.group(1)), path))
    # newest first, falling back past garbled/failed rounds (a failed
    # round commits "parsed": null) so one bad file cannot disarm the
    # guard for the round after it
    for _, path in sorted(rounds, reverse=True):
        try:
            with open(path, "r", encoding="utf-8") as f:
                data = json.load(f)
        except (OSError, ValueError):
            continue
        if not isinstance(data, dict):
            continue
        parsed = data.get("parsed", data)
        if isinstance(parsed, dict) and isinstance(parsed.get("detail"),
                                                   dict):
            return (os.path.basename(path), parsed.get("platform"),
                    parsed["detail"])
    return None


def trend_guard(detail: dict, platform: str | None, repo: str,
                threshold_pct: float = TREND_THRESHOLD_PCT) -> dict | None:
    """Compare this run's device encode/decode GB/s with the previous
    round. Returns the trend record for the JSON line: per-key
    prev/now/regression_pct, the worst regression as `regression_pct`,
    and a `warning` when the drop exceeds `threshold_pct`. None when no
    prior round exists; comparison is skipped (recorded, not silent)
    when the platform changed — cpu-fallback vs tpu GB/s is noise, not
    a regression."""
    prev = previous_bench(repo)
    if prev is None:
        return None
    prev_name, prev_platform, prev_detail = prev
    trend: dict = {"baseline_round": prev_name,
                   "threshold_pct": threshold_pct}
    if prev_platform != platform:
        trend["skipped"] = (f"platform changed "
                            f"({prev_platform} -> {platform}): device "
                            f"GB/s not comparable across backends")
        return trend
    deltas: dict = {}
    worst_pct, worst_key = 0.0, None
    for key, higher_is_worse in \
            [(k, False) for k in TREND_KEYS] \
            + [(k, True) for k in TREND_KEYS_COST]:
        now, old = detail.get(key) or 0.0, prev_detail.get(key) or 0.0
        if not now or not old:
            continue            # one side unmeasured: nothing to judge
        pct = round(((now - old) if higher_is_worse else (old - now))
                    / old * 100.0, 2)
        deltas[key] = {"prev": old, "now": now, "regression_pct": pct}
        if pct > worst_pct:
            worst_pct, worst_key = pct, key
    trend["deltas"] = deltas
    trend["regression_pct"] = worst_pct
    if worst_key is not None and worst_pct > threshold_pct:
        d = deltas[worst_key]
        verb = "rose" if worst_key in TREND_KEYS_COST else "dropped"
        trend["warning"] = (
            f"{worst_key} {verb} {worst_pct}% vs {prev_name} "
            f"({d['prev']} -> {d['now']}, threshold "
            f"{threshold_pct}%) — bisect before merging")
    return trend


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--stage", choices=["cpu", "probe", "device",
                                       "cluster", "cluster_tpu",
                                       "attribution", "failure_storm",
                                       "swarm", "qos_storm",
                                       "scrub_storm",
                                       "mesh_scaling",
                                       "proc_scaling",
                                       "interleave"],
                   required=True)
    args = p.parse_args()
    out = {"cpu": stage_cpu, "probe": stage_probe,
           "device": stage_device, "cluster": stage_cluster,
           "cluster_tpu": stage_cluster_tpu,
           "attribution": stage_attribution,
           "failure_storm": stage_failure_storm,
           "swarm": stage_swarm,
           "qos_storm": stage_qos_storm,
           "scrub_storm": stage_scrub_storm,
           "mesh_scaling": stage_mesh_scaling,
           "proc_scaling": stage_proc_scaling,
           "interleave": stage_interleave}[args.stage]()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
