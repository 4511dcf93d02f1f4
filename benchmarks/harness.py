"""One run of one cell: boot the deployment, warm it up, lay a window
over a closed loop of clients, compare everything with the plain
reference, reduce counters, spans and the profiler's trace to metrics.

Everything here is a plain function or coroutine so that the tests can
drive it tiny on the CPU backend; only `benchmarks.run` looks for the
chip. What belongs to one configuration, one traffic mix or one
per-layer metric is a file of its own, found by the name in
BENCHMARK.json (`load_cell`).
"""
from __future__ import annotations

import asyncio
import dataclasses
import gc
import importlib.util
import json
import math
import os
import shutil
import statistics
import time

import numpy as np

from benchmarks import reference

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
CONTROLS = ("flip_read", "bitrot", "torn_store")
SAMPLED = 8
OP_KINDS = ("write", "read")
STORES = ("memstore", "filestore", "bluestore")
EVENT_KINDS = ("stop_osd", "start_osd", "osd_out", "osd_in")


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    generator: object            # module with make(config, traffic, seed)
    end_to_end: list[dict]
    readers: list[object]        # modules with NAME, UNIT, read(ctx)


def _load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def _load_module(root: str, kind: str, name: str):
    """benchmarks/<kind>/<name>.py under `root`, by its path: a later
    PR's new generator or reader is found the same way as the first."""
    path = os.path.join(root, "benchmarks", kind, name + ".py")
    spec = importlib.util.spec_from_file_location(
        f"benchmarks.{kind}.{name}", path)
    if spec is None or not os.path.exists(path):
        raise SystemExit(f"benchmark: no file {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_cell(workload: str, root: str = ROOT) -> Cell:
    """Resolve a `workloads` entry of BENCHMARK.json to its files. A
    later PR adds files and entries; nothing here names a cell."""
    bench = _load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"benchmark: no workload {workload!r} in "
                         f"BENCHMARK.json (has {sorted(cells)})")
    w = cells[workload]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[w["config"]]
    config = _load_json(os.path.join(root, cfg_entry["file"]))
    traffic = _load_json(os.path.join(
        root, "benchmarks", "traffic", w["traffic"] + ".json"))
    generator = _load_module(root, "generators", config["generator"])

    def applies(metric: dict) -> bool:
        return "workloads" not in metric or workload in metric["workloads"]

    readers = []
    for m in bench["per_layer"]:
        if not applies(m):
            continue
        mod = _load_module(root, "layer_metrics", m["name"])
        for attr, key in (("NAME", "name"), ("UNIT", "unit"),
                          ("LAYER", "layer"), ("MOVES", "moves")):
            if getattr(mod, attr) != m[key]:
                raise SystemExit(
                    f"benchmark: reader {m['name']}: {attr} "
                    f"{getattr(mod, attr)!r} != BENCHMARK.json's "
                    f"{m[key]!r}")
        readers.append(mod)
    return Cell(workload, int(w["chips"]), config, traffic, generator,
                [m for m in bench["end_to_end"] if applies(m)], readers)


# -- counters -----------------------------------------------------------------

def _snapshot(svc) -> dict:
    """Every count the readers use, read at one instant on the loop."""
    from ceph_tpu.msg.messenger import msgr_perf
    from ceph_tpu.utils import copytrack

    return {"t": time.perf_counter(),
            "loop_cpu_s": time.thread_time(),
            "process_cpu_s": time.process_time(),
            "gc_collections": [g["collections"] for g in gc.get_stats()],
            "msgr": dict(msgr_perf().dump()),
            "offload": dict(svc.stats),
            "devices": svc.device_snapshot(),
            "copy": copytrack.snapshot()["stages"]}


class _FlightWatch:
    """OSDs the mon marked down, read from the flight recorder as the
    run goes (the smoke reads it once, at the end). The recorder is a
    ring of 512 events and every op slower than a second adds one, so a
    long window could push a mark-down out before it was read: `poll`
    is called twice a second, and events lost all the same are counted
    and fail the run."""

    def __init__(self):
        from ceph_tpu.utils import flight

        self._flight = flight
        self.cursor = flight.last_seq()
        self.marked: set[str] = set()
        self.lost = 0

    def poll(self) -> None:
        events = self._flight.events_since(self.cursor)["events"]
        last = events[-1]["seq"] if events else self.cursor
        self.lost += (last - self.cursor) - len(events)
        self.cursor = last
        self.marked |= {e["entity"] for e in events
                        if e["type"] == "osd_markdown"}

    async def run(self) -> None:
        while True:
            self.poll()
            await asyncio.sleep(0.5)


def _shard_places(osds, pool: str, oid: str):
    """(osd, shard index, collection, object) for each place where one
    of `osds` would hold a shard of `oid`."""
    for osd in osds:
        for pg in osd.pgs.values():
            if pg.pool.name != pool:
                continue
            if osd.whoami not in pg.acting:
                continue        # a PG it has left: marked out, or a spare
            yield (osd, pg.acting.index(osd.whoami), pg.backend.coll(),
                   pg.backend.ghobject(oid))


def _shard_blobs(osds, pool: str, oid: str) -> dict[int, bytes]:
    """shard index -> the blob that shard's OSD holds for `oid` (copied
    from chip_smoke.py)."""
    return {shard: bytes(osd.store.read(cid, gh))
            for osd, shard, cid, gh in _shard_places(osds, pool, oid)
            if osd.store.exists(cid, gh)}


def _shards_differ(blobs: dict[int, bytes], values: list[bytes], k: int,
                   m: int, chunk: int, absent_counts: bool) -> int:
    """Bytes by which the shards in `blobs` differ from the reference's
    for the candidate value that fits them best. A shard that `blobs`
    lacks counts its whole length where `absent_counts`."""
    best = None
    for value in values:
        want = reference.expected_shards(value, k, m, chunk)
        diff = 0
        for shard in range(k + m):
            if shard not in blobs:
                diff += want.shape[1] if absent_counts else 0
                continue
            have = np.frombuffer(blobs[shard], dtype=np.uint8)
            diff += want.shape[1] if have.size != want.shape[1] else \
                int(np.count_nonzero(have != want[shard]))
        best = diff if best is None else min(best, diff)
    return best


def _sample(model, seed: int) -> list[str]:
    """The objects compared once the window has closed, drawn from the
    seed."""
    rng = np.random.default_rng([seed, 5])
    names = model.names()
    return [names[i] for i in sorted(rng.choice(
        len(names), size=min(SAMPLED, len(names)), replace=False))]


def _flip_a_bit(data: bytes) -> bytes:
    """Control `flip_read`: an answer altered where it is produced."""
    return data[:-1] + bytes([data[-1] ^ 1])


# -- latency arithmetic ---------------------------------------------------------

def percentile(sorted_vals: list[float], q: float) -> float:
    """Nearest rank: the smallest value with at least q of the sample
    at or below it."""
    if not sorted_vals:
        raise ValueError("percentile of an empty sample")
    rank = max(1, math.ceil(q * len(sorted_vals)))
    return sorted_vals[rank - 1]


def series(records: list[tuple], t_open: float, t_close: float) -> dict:
    """Per-second completions and median latency inside the window."""
    n = max(1, math.ceil(t_close - t_open))
    lat: list[list[float]] = [[] for _ in range(n)]
    for _kind, t0, t1, _ok in records:
        if t0 >= t_open and t1 <= t_close:
            lat[min(n - 1, int(t1 - t_open))].append((t1 - t0) * 1e3)
    return {"completions": [len(s) for s in lat],
            "p50_ms": [statistics.median(s) if s else None for s in lat]}


# -- the run ----------------------------------------------------------------------

class _Run:
    """State the clients share. The window is two timestamps laid over
    clients that never stop: it opens when the last warm-up op
    completes and closes `seconds` later."""

    def __init__(self):
        self.issued = 0
        self.warm_done = 0
        self.t_open: float | None = None
        self.t_close: float | None = None
        self.closed = False
        self.records: list[tuple] = []      # (kind, t0, t1, ok)
        self.failures: list[str] = []
        self.read_mismatches = 0
        self.encode_bytes = 0               # padded bytes of acked writes
        self.user_bytes = {k: 0 for k in OP_KINDS}   # completed in window
        self.completed = 0
        self.flip_pending = False
        self.snap_open: dict | None = None
        self.snap_close: dict | None = None
        self.setup_s = 0.0
        self.phases: dict[str, float] = {}  # seconds since process start
        self.compiles: list[float] = []     # perf_counter of each event
        self.events: list[dict] = []        # the schedule's, as each ended
        self.store_dirs: list[str] = []     # a persistent store's, to remove
        self.store_dir_bytes: int | None = None   # what they held at the end


async def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
                   out_dir: str, t_start: float,
                   control: tuple[str, ...] = ()) -> dict:
    """The whole run; returns the object `benchmarks.run` prints as the
    last line. `t_start` is `time.monotonic()` at process start."""
    import jax

    for c in control:
        if c not in CONTROLS:
            raise SystemExit(f"benchmark: unknown control {c!r}")
    if "torn_store" in control and cell.config["objectstore"] == "memstore":
        raise SystemExit("benchmark: control torn_store wants a persistent "
                         "store and the configuration's is memstore")
    device = jax.devices()[0]
    os.makedirs(out_dir, exist_ok=True)
    run = _Run()

    def on_compile(event: str, duration: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            run.compiles.append(time.perf_counter())
    jax.monitoring.register_event_duration_secs_listener(on_compile)
    try:
        return await _run_cell(cell, seed, seconds, trace, out_dir, t_start,
                               control, run, device)
    finally:
        jax.monitoring.unregister_event_duration_listener(on_compile)
        for path in run.store_dirs:
            shutil.rmtree(path, ignore_errors=True)


async def _run_cell(cell, seed, seconds, trace, out_dir, t_start, control,
                    run, device) -> dict:
    import jax

    from ceph_tpu import offload
    from ceph_tpu.tools.cluster_boot import ephemeral_cluster
    from ceph_tpu.utils import tracer
    from ceph_tpu.utils.config import ConfigError

    factory = store_factory(cell.config["objectstore"], run.store_dirs)
    events = schedule_of(cell.traffic)
    n_stop = cell.traffic.get("stop_osds", 0)
    victims = draw_victims(seed, cell.config["osds"], n_stop, events)
    gen = cell.generator.make(cell.config, cell.traffic, seed)
    pool_cfg = cell.config["pool"]
    k, m, chunk = pool_cfg["k"], pool_cfg["m"], pool_cfg["stripe_unit"]
    width = k * chunk
    model = reference.ObjectModel()
    pool = "bench"
    watch = _FlightWatch()
    loop = asyncio.get_running_loop()

    def phase(name: str) -> None:
        run.phases[name] = time.monotonic() - t_start
    phase("backend_and_payloads")

    async with ephemeral_cluster(cell.config["osds"], prefix="bench-",
                                 store_factory=factory) \
            as (client, osds, mon):
        # defaults the mon reads when a pool is created, before it is
        for key, value in cell.config.get("mon_config", {}).items():
            try:
                mon.config.set(key, value)
            except ConfigError as e:
                raise SystemExit(f"benchmark: mon_config: {e}")
        profile = {"plugin": pool_cfg["plugin"], "k": str(k), "m": str(m),
                   "technique": pool_cfg["technique"]}
        await client.command({"prefix": "osd erasure-code-profile set",
                              "name": "benchprof", "profile": profile})
        await client.pool_create(pool, pg_num=pool_cfg["pg_num"],
                                 pool_type="erasure",
                                 erasure_code_profile="benchprof")
        phase("cluster_boot")
        # the deployment's own settings, through the daemons' config
        for osd in osds:
            for key, value in cell.config.get("osd_config", {}).items():
                osd.config.set(key, value)
        io = client.ioctx(pool)
        watching = loop.create_task(watch.run())
        svc = offload.get_service()
        if svc.enabled != bool(cell.config["offload_service"]):
            raise SystemExit("benchmark: the offload service's default "
                             "differs from the configuration's")

        def padded(nbytes: int) -> int:
            return max(1, -(-nbytes // width)) * width

        # compile, or load from the cache, every program a device batch
        # of this cell can need: a bucket flushes once it holds
        # `max_batch_bytes`, and no more ops than clients are in flight
        stripes = padded(gen.object_bytes) // width
        most = min(gen.clients, -(-svc.max_batch_bytes // (stripes * width)))
        _encode_direct(profile, jax.local_devices(), k, chunk, stripes,
                       range(1, most + 1))

        phase("plugin_warm_up")

        async def do_op(op: tuple) -> tuple:
            kind, name, version = op
            ok = True
            if kind == "write":
                value = gen.value_of(name, version)
                model.begin_write(name, version)
                t0 = time.perf_counter()
                try:
                    await io.write_full(name, value)
                except Exception as e:   # the op failed; the run goes on
                    ok = False
                    run.failures.append(f"write {name}: {e!r}")
                t1 = time.perf_counter()
                if ok:
                    model.ack_write(name, version)
                    run.encode_bytes += padded(len(value))
                nbytes = len(value)
            else:
                snap = model.begin_read(name)
                t0 = time.perf_counter()
                try:
                    got = await io.read(name)
                except Exception as e:
                    ok, got = False, b""
                    run.failures.append(f"read {name}: {e!r}")
                t1 = time.perf_counter()
                versions = model.end_read(name, snap)
                if ok:
                    if run.flip_pending and run.t_open is not None \
                            and t0 >= run.t_open:
                        run.flip_pending = False
                        got = _flip_a_bit(got)
                    if not any(got == gen.value_of(name, v)
                               for v in versions):
                        ok = False
                        run.read_mismatches += 1
                nbytes = len(got)
            # counters are read at the window's edges, so what they are
            # divided by counts every op that completed between them
            if run.t_open is not None and run.t_close is None:
                run.user_bytes[kind] += nbytes
                run.completed += 1
            return (kind, t0, t1, ok)

        # preload: a fixed list, with the cell's own clients, to the end
        todo = iter(gen.preload())

        async def loader() -> None:
            for op in todo:
                rec = await do_op(op)
                if not rec[3]:
                    raise SystemExit(f"benchmark: preload failed: "
                                     f"{run.failures[-1]}")
        await asyncio.gather(*[loader() for _ in range(gen.clients)])

        phase("preload")
        down = set(await stop_osds(n_stop, seed, osds, client))
        harness_stopped = set(down)     # at any time; `down` is now
        timers: list[asyncio.TimerHandle] = []
        event_tasks: list[asyncio.Task] = []

        async def run_event(e: dict) -> None:
            """One entry of the schedule, as a task of its own. It is
            recorded once it has ended; one that raises is not."""
            i = victims[e["osd"]]
            try:
                with tracer.span("bench_event") as sp:
                    if sp is not None:
                        sp.set_tag("do", e["do"])
                        sp.set_tag("osd", i)
                    if e["do"] == "stop_osd":
                        down.add(i)
                        harness_stopped.add(i)
                        await osds[i].stop()
                    elif e["do"] == "start_osd":
                        if i not in down:
                            raise RuntimeError(f"osd.{i} is running")
                        await revive_osd(osds, i, mon, cell.config.get(
                            "osd_config", {}))
                        down.discard(i)
                    else:       # the mon's command of that name
                        await client.command({
                            "prefix": e["do"].replace("_", " "),
                            "ids": [i]})
            except Exception as exc:    # a check row; the run goes on
                run.failures.append(f"event {e['do']} osd.{i}: {exc!r}")
                return
            run.events.append({"do": e["do"], "osd": i,
                               "t_s": time.perf_counter() - run.t_open})

        # the profiler's trace is read for TPU planes only; on another
        # backend (the tests) a traced run still reads spans and counters
        profiling = trace and device.platform == "tpu"
        if trace:
            tracer.enable(max_spans=4_000_000)
            span_cursor = tracer.collector().last_seq()
            profile_dir = os.path.join(out_dir, "trace")
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            options.host_tracer_level = 2
        window_mark = None

        def open_window() -> None:
            nonlocal window_mark
            gc.collect()
            if profiling:
                jax.profiler.start_trace(profile_dir,
                                         profiler_options=options)
                window_mark = jax.profiler.TraceAnnotation("bench_window")
                window_mark.__enter__()
                if cell.traffic.get("device_touch"):
                    # a mix that bypasses the codec still shows a live
                    # device in its trace: one job of the cell's shape
                    with jax.profiler.TraceAnnotation("bench_device_touch"):
                        _encode_direct(profile, [device], k, chunk,
                                       stripes, range(1, 2))
            run.flip_pending = "flip_read" in control
            if trace:
                with tracer.span("bench_open"):
                    pass
            run.snap_open = _snapshot(svc)
            run.t_open = run.snap_open["t"]
            run.setup_s = time.monotonic() - t_start
            for e in events:
                timers.append(loop.call_later(
                    e["at_s"], lambda e=e: event_tasks.append(
                        loop.create_task(run_event(e)))))
            loop.call_later(seconds, close_window)

        def close_window() -> None:
            run.snap_close = _snapshot(svc)
            run.t_close = run.snap_close["t"]
            if trace:
                with tracer.span("bench_close"):
                    pass
            if window_mark is not None:
                window_mark.__exit__(None, None, None)
            run.closed = True

        async def client_loop() -> None:
            while not run.closed:
                idx = run.issued
                run.issued += 1
                rec = await do_op(gen.next_op())
                if idx < gen.warmup_ops:
                    run.warm_done += 1
                    if run.warm_done == gen.warmup_ops:
                        open_window()
                else:
                    run.records.append(rec)

        await asyncio.gather(*[client_loop() for _ in range(gen.clients)])
        for timer in timers:        # one due after the close never ran
            timer.cancel()
        if event_tasks:
            _done, late = await asyncio.wait(event_tasks, timeout=60)
            for task in late:
                task.cancel()
            await asyncio.gather(*late, return_exceptions=True)
        await svc.drain()
        if profiling:
            jax.profiler.stop_trace()
        if trace:
            tracer.disable()

        # -- what the reference says ------------------------------------------
        t_open, t_close = run.t_open, run.t_close
        started = [r for r in run.records if r[1] >= t_open
                   and r[1] < t_close]
        good = [r for r in started if r[2] <= t_close and r[3]]
        lat = sorted((r[2] - r[1]) * 1e3 for r in good)
        stopped = sorted(down)      # not running when the window closed
        checks = await final_checks(
            cell, gen, model, io, osds, pool, seed, control, run,
            k, m, chunk, stopped, watch, svc, device.platform,
            harness_stopped)
        watching.cancel()
        await asyncio.gather(watching, return_exceptions=True)
        checks.insert(0, ("ops_failed", sum(not r[3] for r in started), 0))
        checks.insert(1, ("read_mismatches", run.read_mismatches, 0))
        window_s = t_close - t_open
        checks.insert(2, ("events_failed", len(events) - sum(
            e["t_s"] <= window_s for e in run.events), 0))

        metrics: dict[str, dict] = {}
        if not trace:
            values = {"ops_s": len(good) / window_s,
                      "op_p50_ms": percentile(lat, 0.5) if lat else None,
                      "op_p95_ms": percentile(lat, 0.95) if lat else None,
                      "setup_s": run.setup_s}
            for e in cell.end_to_end:
                if values.get(e["name"]) is not None:
                    metrics[e["name"]] = {"value": values[e["name"]],
                                          "unit": e["unit"]}
        in_window = [t for t in run.compiles if t_open <= t <= t_close]
        reduced = None
        if trace:
            from benchmarks import trace_reduce
            if profiling:
                reduced = trace_reduce.reduce_dir(profile_dir)
            spans = [s for s in tracer.collector().spans()
                     if s["seq"] > span_cursor]
            await asyncio.sleep(max(0.0, 2.2 - (time.perf_counter()
                                                - t_close)))
            ctx = Ctx(cell=cell, window_s=window_s,
                      ops=run.completed, user_bytes=dict(run.user_bytes),
                      open=run.snap_open, close=run.snap_close,
                      spans=window_spans(spans),
                      trace=reduced, compiles_in_window=len(in_window),
                      device_kind=device.device_kind,
                      platform=device.platform,
                      peaks=trace_reduce.peaks_for(device.device_kind)
                      if device.platform == "tpu" else None,
                      store_bytes=sum(o.store.used_bytes() for o in osds
                                      if o.whoami not in stopped),
                      live_user_bytes=sum(
                          len(gen.value_of(n, max(model.candidates(n))))
                          for n in model.names()),
                      events=list(run.events))
            for mod in cell.readers:
                value = mod.read(ctx)
                if value is not None:
                    metrics[mod.NAME] = {"value": value, "unit": mod.UNIT}

        ser = series(run.records, t_open, t_close)
        with open(os.path.join(out_dir, "series.json"), "w") as f:
            json.dump({"workload": cell.name, "seed": seed,
                       "seconds": seconds, "trace": int(trace),
                       "window_s": window_s, "setup_s": run.setup_s, **ser},
                      f)
        if factory is not None:
            # last, since it stops the daemons: nothing reads them after
            checks.append(await lost_on_remount(
                gen, model, osds, pool, seed, control, run, k, m, chunk,
                stopped))

    correct = all(value <= limit for _n, value, limit in checks)
    stats = device.memory_stats() or {}
    dev = {"platform": device.platform, "kind": device.device_kind,
           "count": len(jax.devices()),
           "memory_peak_bytes": int(stats.get("peak_bytes_in_use", 0))}
    if reduced is not None:
        dev["busy_s"] = reduced["busy_s"]
        dev["window_s"] = reduced["window_s"]
    o, c = run.snap_open, run.snap_close
    info = {"samples": len(lat), "window_s": window_s,
            "setup_phases_end_s": run.phases,
            "loop_busy_pct": 100 * (c["loop_cpu_s"] - o["loop_cpu_s"])
            / window_s,
            "process_cpu_pct": 100 * (c["process_cpu_s"]
                                      - o["process_cpu_s"]) / window_s,
            "gc_collections": [b - a for a, b in zip(o["gc_collections"],
                                                     c["gc_collections"])],
            "offload_batches": c["offload"]["batches"]
            - o["offload"]["batches"],
            "offload_jobs": c["offload"]["jobs"] - o["offload"]["jobs"],
            "compiles_in_window": len(in_window),
            "compile_events": len(run.compiles),
            "convoy": ser["completions"][:8],
            "events": run.events,
            "store_dir_bytes": run.store_dir_bytes,
            "failures": run.failures[:5]}
    out = {"correct": correct, "attempted": len(started),
           "failed": sum(not r[3] for r in started),
           "metrics": metrics, "device": dev}
    if reduced is not None:
        out["breakdown"] = reduced["breakdown"]
    return {"result": out, "checks": checks, "info": info}


@dataclasses.dataclass
class Ctx:
    """What a per-layer reader may read. `open` and `close` are the
    counter snapshots at the window's edges; `spans` are the program's
    spans that lie inside the window; `trace` is the reduced profile;
    `ops` and `user_bytes` count the ops that completed in the window;
    `events` are the traffic file's that ran: `do`, the OSD's id, and
    `t_s`, the seconds after the window opened at which it had ended."""
    cell: Cell
    window_s: float
    ops: int
    user_bytes: dict
    open: dict
    close: dict
    spans: dict
    trace: dict | None
    compiles_in_window: int
    device_kind: str
    platform: str
    peaks: dict | None
    store_bytes: int
    live_user_bytes: int
    events: list[dict]

    def delta(self, group: str, key: str) -> float:
        return self.close[group][key] - self.open[group][key]

    def device_delta(self, key: str) -> float:
        """Sum over this platform's device lanes of the offload
        service's per-device counter `key`, inside the window."""
        total = 0.0
        for label, after in self.close["devices"].items():
            if label.startswith(self.platform + ":"):
                before = self.open["devices"].get(label, {})
                total += after[key] - before.get(key, 0)
        return total


def window_spans(spans: list[dict]) -> dict:
    """name -> the program's spans that began and ended inside the
    window. The harness drops a marker span at each edge, so the edges
    are read on the tracer's own clock."""
    lo = next(s["start"] for s in spans if s["name"] == "bench_open")
    hi = next(s["start"] for s in spans if s["name"] == "bench_close")
    by: dict[str, list[dict]] = {}
    for s in spans:
        if s["start"] >= lo and s["start"] + s["duration_us"] / 1e6 <= hi:
            by.setdefault(s["name"], []).append(s)
    return by


def _encode_direct(profile: dict, devices, k: int, chunk: int,
                   stripes: int, jobs: range) -> None:
    """Call the pool's plugin directly, without touching the pool: one
    call for each number of jobs in `jobs` on each of `devices`, on the
    device array the offload service would hand it."""
    import jax

    from ceph_tpu.ec.registry import ErasureCodePluginRegistry

    code = ErasureCodePluginRegistry.instance().factory(
        profile["plugin"], dict(profile))
    for n in jobs:
        batch = np.zeros((n * stripes, k, chunk), dtype=np.uint8)
        for device in devices:
            np.asarray(code.encode_stripes(jax.device_put(batch, device)))


def store_factory(kind: str, made: list[str]):
    """A configuration's `objectstore` as `ephemeral_cluster` takes it:
    nothing for `memstore` (its own default), else a store of that name
    in a directory of its own under the cluster's temporary directory.
    `made` collects the directories, for the run to remove."""
    if kind not in STORES:
        raise SystemExit(f"benchmark: objectstore {kind!r} is not one of "
                         f"{STORES}")
    if kind == "memstore":
        return None
    if kind == "filestore":
        from ceph_tpu.objectstore.filestore import FileStore as Store
    else:
        from ceph_tpu.objectstore.bluestore import BlueStore as Store

    def factory(tmpdir: str, osd_id: int):
        made.append(os.path.join(tmpdir, f"osd{osd_id}"))
        return Store(made[-1])
    return factory


def schedule_of(traffic: dict) -> list[dict]:
    """A traffic file's `events`, each `{"at_s", "do", "osd"}`: `at_s`
    seconds after the window opens, `do` one of `EVENT_KINDS`, `osd` an
    index into the victims the seed draws (`draw_victims`)."""
    events = traffic.get("events", [])
    for e in events:
        if set(e) != {"at_s", "do", "osd"}:
            raise SystemExit(f"benchmark: event {e!r} has other keys than "
                             f"at_s, do, osd")
        if e["do"] not in EVENT_KINDS:
            raise SystemExit(f"benchmark: event {e!r}: do {e['do']!r} is "
                             f"not one of {EVENT_KINDS}")
        if not (isinstance(e["at_s"], (int, float)) and e["at_s"] >= 0):
            raise SystemExit(f"benchmark: event {e!r}: at_s "
                             f"{e['at_s']!r} is no time at or after 0")
        if not isinstance(e["osd"], int) or e["osd"] < 0:
            raise SystemExit(f"benchmark: event {e!r}: osd {e['osd']!r} "
                             f"is no index")
    return events


def draw_victims(seed: int, n_osds: int, stop: int,
                 events: list[dict]) -> list[int]:
    """The OSD ids a mix's failures fall on. The first `stop` are the
    ones set-up stops, the draw `stop_osds` has always made; the events
    index the same list, which goes on through the other OSDs in an
    order drawn after it, as far as they reach."""
    need = max([stop] + [e["osd"] + 1 for e in events])
    if need > n_osds:
        raise SystemExit(f"benchmark: the mix names {need} OSDs and the "
                         f"deployment has {n_osds}")
    if not need:
        return []
    rng = np.random.default_rng([seed, 4])
    victims = sorted(int(x) for x in rng.choice(
        n_osds, size=stop, replace=False)) if stop else []
    rest = [int(x) for x in rng.permutation(n_osds) if x not in victims]
    return victims + rest[:need - stop]


async def revive_osd(osds: list, i: int, mon, osd_config: dict) -> None:
    """A new daemon of the stopped osd.`i` on its store, with the
    deployment's settings: a thrasher's revive (`OSD.start` does not
    run twice on one object). It takes the old one's place in `osds`
    before it starts, so the cluster's teardown reaps it either way."""
    from ceph_tpu.osd.daemon import OSD

    old = osds[i]
    osds[i] = OSD(i, list(mon.monmap.mons.values()), store=old.store,
                  crush_location=old.crush_location)
    for key, value in osd_config.items():
        osds[i].config.set(key, value)
    await osds[i].start()


async def stop_osds(n: int, seed: int, osds, client) -> list[int]:
    """Stop `n` OSDs drawn from the seed and wait until every map says
    so (a mix's failure to inject; copied from chip_smoke.py)."""
    dead = draw_victims(seed, len(osds), n, [])
    for i in dead:
        await osds[i].stop()
    alive = [o for o in osds if o.whoami not in dead]
    deadline = time.monotonic() + 60
    while not all(i in mp.osds and not mp.osds[i].up
                  for mp in [o.osdmap for o in alive] + [client.osdmap]
                  for i in dead):
        if time.monotonic() > deadline:
            raise TimeoutError(f"osds {dead} never marked down")
        await asyncio.sleep(0.1)
    return dead


def _dir_bytes(path: str) -> int:
    return sum(os.path.getsize(os.path.join(d, f))
               for d, _dirs, files in os.walk(path) for f in files)


def _tear(path: str) -> None:
    """Control `torn_store`: every file under a store's directory loses
    its second half (the block file, the KV's runs and log, a journal,
    a blob), as if what was acknowledged had never reached them."""
    for d, _dirs, files in os.walk(path):
        for f in files:
            full = os.path.join(d, f)
            os.truncate(full, os.path.getsize(full) // 2)


async def lost_on_remount(gen, model, osds, pool, seed, control, run,
                          k, m, chunk, stopped) -> tuple:
    """A persistent store's guarantee, as far as a run can show it: an
    acknowledged write is on the store. The running daemons are stopped
    as a kill would stop them (no `umount`; each keeps its store object,
    so nothing in user space is closed and flushed on the way), a new
    store of the same class is mounted on each directory, and the
    sampled objects' shards are read from it and compared with the
    reference's, as the live ones were. Returns the check row: the
    bytes that differ or cannot be read, limit 0."""
    from ceph_tpu.utils.async_util import bounded_stop

    live = [o for o in osds if o.whoami not in stopped]
    sample = _sample(model, seed)
    places = {name: [(osd, shard, cid, gh)
                     for osd, shard, cid, gh in _shard_places(live, pool, name)
                     if osd.store.exists(cid, gh)]
              for name in sample}
    for osd in live:
        osd.store.umount = lambda: None     # a kill unmounts nothing
        await bounded_stop(osd.stop(), 60.0)
    run.store_dir_bytes = sum(_dir_bytes(osd.store.path) for osd in live)
    if "torn_store" in control:
        holder = places[sample[0]][0][0]
        _tear(holder.store.path)
    fresh: dict[int, object] = {}
    for osd in live:
        try:
            fresh[osd.whoami] = type(osd.store)(osd.store.path)
            fresh[osd.whoami].mount()
        except Exception as e:      # a store that does not mount holds nothing
            run.failures.append(f"remount osd.{osd.whoami}: {e!r}")
            fresh[osd.whoami] = None
    lost = 0
    for name in sample:
        blobs: dict[int, bytes] = {}
        for osd, shard, cid, gh in places[name]:
            blobs[shard] = b""      # unreadable: every byte of it is lost
            if fresh[osd.whoami] is None:
                continue
            try:
                blobs[shard] = bytes(fresh[osd.whoami].read(cid, gh))
            except Exception as e:
                run.failures.append(f"remount osd.{osd.whoami} shard "
                                    f"{shard} of {name}: {e!r}")
        values = [gen.value_of(name, v) for v in model.candidates(name)]
        lost += _shards_differ(blobs, values, k, m, chunk,
                               absent_counts=False)
    return ("shard_bytes_lost_on_remount", lost, 0)


async def final_checks(cell, gen, model, io, osds, pool, seed, control, run,
                       k, m, chunk, stopped, watch, svc,
                       platform, harness_stopped) -> list[tuple]:
    """After the window: sampled objects read back and their shards at
    rest compared with the reference; the smoke's gates on the offload
    counters. Returns (name, value, limit) rows; all limits are exact.
    `stopped` are the OSDs not running now; `harness_stopped` those the
    harness stopped at any time, whose mark-downs are its own doing."""
    sample = _sample(model, seed)
    if "bitrot" in control:
        # the program's own fault path: one byte of one shard at rest
        for osd in osds:
            res = await osd._inject_bitrot(sample[0])
            if "injected" in res:
                break
        else:
            raise SystemExit("benchmark: control bitrot found no shard")
    sample_mismatches = 0
    shard_bytes_differing = 0
    for name in sample:
        values = [gen.value_of(name, v) for v in model.candidates(name)]
        got = await io.read(name)
        if run.flip_pending:
            run.flip_pending = False
            got = _flip_a_bit(got)
        if got not in values:
            sample_mismatches += 1
        blobs = _shard_blobs([o for o in osds if o.whoami not in stopped],
                             pool, name)
        # a shard may be missing only with an OSD stopped (its own, or
        # one whose shards a spare now takes)
        shard_bytes_differing += _shards_differ(blobs, values, k, m, chunk,
                                                absent_counts=not stopped)
    off = svc.stats
    devices = svc.device_snapshot()
    on_device = sum(s["bytes"] for d, s in devices.items()
                    if d.startswith(platform + ":"))
    strangers = [d for d in devices
                 if d != "host" and not d.startswith(platform + ":")]
    watch.poll()
    marked = watch.marked - {f"osd.{i}" for i in harness_stopped}
    return [("sample_read_mismatches", sample_mismatches, 0),
            ("shard_bytes_differing", shard_bytes_differing, 0),
            ("fallback_ops", off["fallback_ops"], 0),
            ("breaker_trips", off["breaker_trips"], 0),
            ("device_failovers", off["device_failovers"], 0),
            ("osd_markdowns_under_load", len(marked), 0),
            ("flight_events_lost", watch.lost, 0),
            ("lanes_off_platform", len(strangers), 0),
            ("encode_bytes_not_on_device",
             max(0, run.encode_bytes - on_device), 0)]
