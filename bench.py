#!/usr/bin/env python3
"""Driver benchmark entry point: prints ONE JSON line
`{"metric": ..., "value": N, "unit": ..., "vs_baseline": N}`.

Staging: the benchmark is split into independently-timed children, and
a per-stage status record explains exactly what ran. This parent never
imports jax, so it never holds the chip its children need:

  1. `--stage cpu`    CPU-native + numpy baselines under JAX_PLATFORMS=cpu
                      — always yields the vs_baseline denominator.
  2. `--stage device` ONE child for backend init and the device benches,
                      under JAX_PLATFORMS=tpu: a missing or busy chip
                      raises in the child instead of jax falling back to
                      the CPU. A device stage that fails is reported as
                      failed and makes this script exit non-zero; it is
                      never re-run on the CPU under the same keys.

Environment knobs:
  CEPH_TPU_BENCH_TIMEOUT  total budget in seconds (default 2400)
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, REPO)

from ceph_tpu.utils import tracer  # noqa: E402
TOTAL_BUDGET = int(os.environ.get("CEPH_TPU_BENCH_TIMEOUT", "2400"))
# reactor shard knob: the cluster_tpu stage sweeps 1/2/4 shards up to
# this cap, and the attribution stage profiles the sharded runtime
# (same guarded parse as bench_driver._reactor_shards_knob — a
# malformed value must not kill the bench before any stage runs)
try:
    REACTOR_SHARDS = max(1, int(
        os.environ.get("CEPH_TPU_REACTOR_SHARDS", "4")))
except ValueError:
    REACTOR_SHARDS = 4
# process-backed reactor knob: the cluster_tpu stage sweeps 1/2 worker
# PROCESSES up to this cap (the true GIL escape; same guarded parse)
try:
    REACTOR_PROCS = max(1, int(
        os.environ.get("CEPH_TPU_REACTOR_PROCS", "2")))
except ValueError:
    REACTOR_PROCS = 2
CPU_TIMEOUT = 420
DEVICE_TIMEOUT = 300  # one child: backend init (7-14 s on the attached
#                       v5e, as chip_smoke.py prints it) + ~4 s per
#                       compiled program + the benches
CLUSTER_TPU_TIMEOUT = 860  # in-situ EC-over-tpu cluster stage: body
#                            (240) + datapath (120) + reactor shard
#                            curve (180) + process-backed curve (240)
#                            + scaling child headroom
ATTRIBUTION_TIMEOUT = 240  # hermetic attribution-profiler stage
FAILURE_STORM_TIMEOUT = 500  # kill/revive resilience + repair-ratio stage
#                              (280) + cross-process flight-recorder
#                              drill (170) + headroom
SWARM_TIMEOUT = 320  # 200-client multi-tenant fairness + SLO pipeline stage
QOS_STORM_TIMEOUT = 560  # 1000-client sharded storm, scheduler A/B +
#                          recovery-under-storm + shed phase (520 body)
INTERLEAVE_TIMEOUT = 440  # seed-swept schedule explorer + sanitizer AND
#                           flight-recorder overhead (3 modes x 2 reps)
METRIC = "ec_encode_k8m3_1MiB_chunk"

_deadline = time.monotonic() + TOTAL_BUDGET


def _budget(want: float) -> float:
    return max(10.0, min(want, _deadline - time.monotonic()))


def _hermetic_env() -> dict:
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["CEPH_TPU_REACTOR_SHARDS"] = str(REACTOR_SHARDS)
    env["CEPH_TPU_REACTOR_PROCS"] = str(REACTOR_PROCS)
    return env


def _tpu_env() -> dict:
    env = dict(os.environ)
    # explicit: with the variable unset jax falls back to the CPU when
    # the TPU cannot be initialised
    env["JAX_PLATFORMS"] = "tpu"
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["CEPH_TPU_REACTOR_SHARDS"] = str(REACTOR_SHARDS)
    env["CEPH_TPU_REACTOR_PROCS"] = str(REACTOR_PROCS)
    return env


def run_stage(stage: str, env: dict, timeout: float) -> dict:
    """Run one bench_driver stage; returns {"status", "elapsed_s", ...data}."""
    with tracer.span(f"bench:{stage}") as sp:
        out = _run_stage_child(stage, env, timeout)
        if sp is not None:
            sp.set_tag("status", out.get("status"))
            sp.set_tag("platform", out.get("platform"))
        return out


def _run_stage_child(stage: str, env: dict, timeout: float) -> dict:
    t0 = time.monotonic()
    try:
        proc = subprocess.run(
            [sys.executable, "-m", "ceph_tpu.tools.bench_driver",
             "--stage", stage],
            cwd=REPO, env=env, capture_output=True, text=True,
            timeout=timeout)
    except subprocess.TimeoutExpired as e:
        stderr = e.stderr
        if isinstance(stderr, bytes):
            stderr = stderr.decode(errors="replace")
        # a wedged stage is EXACTLY where leaked tasks show up: count
        # them here too, or tail_clean would pass in the worst case
        return {"status": f"timeout after {timeout:.0f}s",
                "elapsed_s": round(time.monotonic() - t0, 1),
                "destroyed_tasks": (stderr or "").count(
                    "Task was destroyed but it is pending"),
                "stderr_tail": (stderr or "")[-800:]}
    except OSError as e:
        return {"status": f"launch failed: {e}",
                "elapsed_s": round(time.monotonic() - t0, 1)}
    sys.stderr.write(proc.stderr)
    # bench-tail cleanliness gate: a stage that destroys pending
    # event-loop tasks ("Task was destroyed but it is pending!", the
    # BENCH_r05 _dispatch_loop spam) is recorded per stage and rolled
    # into the top-level `tail_clean` verdict
    destroyed = proc.stderr.count("Task was destroyed but it is pending")
    # a child that raised printed no result worth merging: only a
    # clean exit can be "ok"
    lines = proc.stdout.strip().splitlines() if proc.returncode == 0 else []
    for candidate in reversed(lines):
        candidate = candidate.strip()
        if candidate.startswith("{"):
            try:
                data = json.loads(candidate)
            except json.JSONDecodeError:
                break
            data["status"] = "ok"
            data["elapsed_s"] = round(time.monotonic() - t0, 1)
            data["destroyed_tasks"] = destroyed
            return data
    return {"status": f"no JSON from child (rc={proc.returncode})",
            "elapsed_s": round(time.monotonic() - t0, 1),
            "destroyed_tasks": destroyed,
            "stderr_tail": proc.stderr[-800:]}


def main() -> int:
    stages: dict[str, object] = {}
    # per-stage spans: the breakdown rides the output JSON as `trace`
    # and prints alongside the GB/s lines
    tracer.enable()

    # Stage 1: CPU baselines — hermetic, hang-proof by construction.
    cpu = run_stage("cpu", _hermetic_env(), _budget(CPU_TIMEOUT))
    stages["cpu"] = cpu

    # Stage 1b: in-situ cluster throughput (rados-bench analog) —
    # hermetic CPU, measures the framework end to end.
    cluster = run_stage("cluster", _hermetic_env(), _budget(240))
    stages["cluster"] = cluster

    # Stage 2: ONE device child — backend init and benches in the same
    # process.
    device = run_stage("device", _tpu_env(), _budget(DEVICE_TIMEOUT))
    stages["device"] = device

    # Stage 3: cluster-EC-over-tpu — the in-situ data path on the device
    # plugin, offload-batched vs per-op inline dispatch (k=8,m=3).
    cluster_tpu = run_stage("cluster_tpu", _tpu_env(),
                            _budget(CLUSTER_TPU_TIMEOUT))
    stages["cluster_tpu"] = cluster_tpu
    device_failed = [name for name, st in (("device", device),
                                           ("cluster_tpu", cluster_tpu))
                     if st.get("status") != "ok"]

    # Stage 4: data-path attribution — the "where the 450x goes"
    # waterfall (queue-wait/copy/H2D/kernel/D2H/commit from real spans,
    # copy amplification, loop busy fraction, per-device utilization).
    # Hermetic: it profiles the FRAMEWORK's data path.
    attribution = run_stage("attribution", _hermetic_env(),
                            _budget(ATTRIBUTION_TIMEOUT))
    stages["attribution"] = attribution

    # Stage 5: failure storm — kill m=3 of 11 OSDs under sustained EC
    # (clay k=8,m=3) client load, degraded reads served throughout,
    # revive, time-to-clean + recovery MB/s + backfill p99, then the
    # single-shard repair-bytes ratio vs the full-stripe baseline.
    # Hermetic: it measures degraded OPERATION, not codec speed.
    storm = run_stage("failure_storm", _hermetic_env(),
                      _budget(FAILURE_STORM_TIMEOUT))
    stages["failure_storm"] = storm

    # Stage 6: many-client swarm — >= 200 concurrent librados clients
    # (mixed sizes, zipfian hot keys, slow-reader overload) against an
    # EC pool with per-client SLO accounting armed: aggregate MB/s,
    # per-client p99 spread, fairness ratio (max/median p99), and the
    # client-observability pipeline verified live (ceph_client_*
    # scrape + SLO_VIOLATIONS fire/mute). Hermetic: it measures
    # multi-tenant FAIRNESS, not codec speed.
    swarm = run_stage("swarm", _hermetic_env(), _budget(SWARM_TIMEOUT))
    stages["swarm"] = swarm

    # Stage 6b: QoS storm — the dmclock scheduler graded A/B under a
    # 1000-client sharded swarm with three adversarial tenants and a
    # paced victim band: fairness ratio + victim p99 + goodput with
    # the arbiter ON vs the legacy WRR path, recovery progressing
    # through its reservation during the storm, and the overload/shed
    # admission-control phase (MOSDOpThrottle + flight crumbs +
    # per-tenant ceph_qos_* counters). Hermetic: it measures
    # arbitration, not codec speed.
    qos = run_stage("qos_storm", _hermetic_env(),
                    _budget(QOS_STORM_TIMEOUT))
    stages["qos_storm"] = qos

    # Stage 7: interlock qa sweep — seeded schedule exploration over a
    # pipelined EC cluster, explorer-only vs explorer+sanitizer
    # (generation guards, lockset recorder): seeds run, distinct
    # schedules explored, and the sanitizer-mode overhead % the trend
    # guard watches. Hermetic: it measures the qa tier's cost, not
    # codec speed.
    ilv = run_stage("interleave", _hermetic_env(),
                    _budget(INTERLEAVE_TIMEOUT))
    stages["interleave"] = ilv

    detail = {k: v for k, v in cpu.items()
              if k not in ("status", "elapsed_s", "stderr_tail")}
    detail.update({k: v for k, v in cluster.items()
                   if k not in ("status", "elapsed_s", "stderr_tail")})
    detail.update({k: v for k, v in cluster_tpu.items()
                   if k not in ("status", "elapsed_s", "stderr_tail",
                                "offload_status")})
    detail.update({k: v for k, v in attribution.items()
                   if k not in ("status", "elapsed_s", "stderr_tail",
                                "attribution")})
    detail.update({k: v for k, v in storm.items()
                   if k not in ("status", "elapsed_s", "stderr_tail")})
    detail.update({k: v for k, v in swarm.items()
                   if k not in ("status", "elapsed_s", "stderr_tail")})
    detail.update({k: v for k, v in qos.items()
                   if k not in ("status", "elapsed_s", "stderr_tail")})
    detail.update({k: v for k, v in ilv.items()
                   if k not in ("status", "elapsed_s", "stderr_tail")})
    detail.update({k: v for k, v in device.items()
                   if k not in ("status", "elapsed_s", "stderr_tail")})

    baseline = detail.get("cpu_native_encode") or 0.0
    baseline_name = "cpu_native_encode (C++ AVX2 split-table, isa stand-in)"
    if not baseline:
        baseline = detail.get("cpu_numpy_encode") or 0.0
        baseline_name = "cpu_numpy_encode (native codec unavailable)"

    value = detail.get("tpu_encode") or 0.0
    vs = round(value / baseline, 3) if baseline > 0 else 0.0
    out = {
        "metric": METRIC,
        "value": value,
        "unit": "GB/s",
        "vs_baseline": vs,
        # cluster observability snapshot (status, check codes,
        # per-daemon report ages) from the cluster stage's health probe
        "health": detail.pop("health", None),
        # the attribution waterfall: queue-wait/copy/H2D/kernel/D2H/
        # commit buckets from real spans, copy amplification, loop
        # busy fraction, per-device utilization
        "attribution": attribution.get("attribution"),
        "baseline": baseline_name,
        "platform": device.get("platform", "none"),
        "reactor_shards": REACTOR_SHARDS,
        "reactor_procs": REACTOR_PROCS,
        "detail": detail,
        "stages": {name: {k: s.get(k) for k in
                          ("status", "elapsed_s", "platform", "backend_init_s",
                           "destroyed_tasks", "stderr_tail")
                          if k in s}
                   for name, s in stages.items()},
        # no stage may leak pending event-loop tasks at teardown — the
        # assertion form of the BENCH_r05 "Task was destroyed" tail fix
        "tail_clean": all(s.get("destroyed_tasks", 0) == 0
                          for s in stages.values()),
    }
    if not out["tail_clean"]:
        leaky = {n: s["destroyed_tasks"] for n, s in stages.items()
                 if s.get("destroyed_tasks")}
        sys.stderr.write(f"bench tail NOT clean: destroyed pending "
                         f"tasks per stage: {leaky}\n")
    if device_failed:
        out["error"] = ("device stage(s) failed: "
                        + ", ".join(f"{n} ({stages[n].get('status')})"
                                    for n in device_failed))
    # bench trend guard: compare device codec GB/s against the newest
    # committed BENCH_r*.json so a silent slide (the r4->r5 35.2->31.96
    # encode drop) becomes a loud regression_pct the round it happens
    from ceph_tpu.tools.bench_driver import trend_guard
    trend = trend_guard(detail, out["platform"], REPO)
    if trend is not None:
        out["trend"] = trend
        out["regression_pct"] = trend.get("regression_pct", 0.0)
        if "warning" in trend:
            sys.stderr.write("bench trend: " + trend["warning"] + "\n")
    # per-stage wall-clock breakdown from the stage spans
    spans = [s for s in tracer.collector().spans()
             if s["name"].startswith("bench:")]
    out["trace"] = [{"stage": s["name"][len("bench:"):],
                     "seconds": round(s["duration_us"] / 1e6, 1),
                     "status": s["tags"].get("status"),
                     "platform": s["tags"].get("platform")}
                    for s in spans]
    sys.stderr.write("stage breakdown: " + " | ".join(
        f"{t['stage']} {t['seconds']}s ({t['status']})"
        for t in out["trace"]) + "\n")
    print(json.dumps(out), flush=True)
    return 1 if device_failed else 0


if __name__ == "__main__":
    sys.exit(main())
