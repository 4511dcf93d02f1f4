"""Process-backed reactor runtime tests: worker spawn/supervise/reap,
the admin-socket control channel (boot/config/inject verbs), a
process-backed cluster round-trip bit-identical to the single-loop
runtime, the SIGKILL -> supervisor-reap -> reporter-quorum-mark-down ->
respawn-rejoin drill, cross-process loopprof attribution keyed by
pool-wide shard index, a worker's loop being unaddressable from the
parent, and the GIL switch interval staying untouched. Every test runs
under the conftest pending-task leak gate, so a parent-side
supervisor/executor leak fails loudly."""
import asyncio
import sys
import time

import pytest

from ceph_tpu.utils import reactor
from ceph_tpu.utils.reactor import ProcShardPool


def run(coro, timeout=180):
    return asyncio.run(asyncio.wait_for(coro, timeout))


# ---------------------------------------------------------------------------
# pool identity + unaddressable worker loops + switch interval
# ---------------------------------------------------------------------------

def test_proc_pool_identity_and_rejected_conveniences():
    async def body():
        default_interval = sys.getswitchinterval()
        pool = ProcShardPool(2, name="t-ident")
        try:
            await pool.start()
            assert pool.num_shards == 3
            # OSDs round-robin over WORKERS only; shard 0 = this loop
            assert [pool.place(i) for i in range(5)] == [1, 2, 1, 2, 1]
            assert pool.loop(0) is asyncio.get_running_loop()
            assert reactor.pool_for(asyncio.get_running_loop()) is pool
            assert reactor.shard_index_of(asyncio.get_running_loop()) == 0
            with pytest.raises(NotImplementedError):
                pool.loop(1)        # another process's loop: unaddressable
            st = await pool.call(1, "worker status")
            assert st["shard"] == 1 and st["pid"] != 0
            assert st["pid"] == pool.worker_pid(1)
            # a pool-wide broadcast onto (momentarily) OSD-less workers
            # is a no-op, not a half-propagated abort
            out = await pool.config_set("osd_heartbeat_grace", 2.0)
            assert all(r["applied"] == [] for r in out.values())
            # a process pool never touches the GIL switch interval:
            # its shards don't share an interpreter
            assert sys.getswitchinterval() == default_interval
        finally:
            await pool.shutdown()
        assert sys.getswitchinterval() == default_interval
        # shutdown gives the parent loop its unpooled answer back
        assert reactor.pool_for(asyncio.get_running_loop()) is None
        # every worker exited through the graceful shutdown verb
        assert all(not pool.worker_alive(i) for i in (1, 2))
    run(body())


# ---------------------------------------------------------------------------
# process-backed cluster: op round-trip bit-identity vs the single loop
# ---------------------------------------------------------------------------

def _cluster_roundtrip(procs: int):
    async def body():
        from ceph_tpu.tools.cluster_boot import ephemeral_cluster
        payloads = {f"o{i}": bytes([i + 1]) * 9000 for i in range(6)}
        got = {}
        workers = []
        async with ephemeral_cluster(
                3, prefix=f"procrt{procs}-",
                reactor_procs=procs) as (client, osds, _mon):
            await client.command({
                "prefix": "osd erasure-code-profile set",
                "name": "rtprof",
                "profile": {"plugin": "jerasure", "k": "2", "m": "1",
                            "technique": "reed_sol_van"}})
            await client.pool_create("rt", pg_num=4,
                                     pool_type="erasure",
                                     erasure_code_profile="rtprof")
            io = client.ioctx("rt")
            for oid, data in payloads.items():
                await io.write_full(oid, data)
            for oid in payloads:
                got[oid] = await io.read(oid)
            if procs > 0:
                pool = osds[0].pool
                workers = [pool._worker(i) for i in (1, 2)]
                # daemons really forked: distinct worker pids, both
                # workers host OSDs, and daemon status reports the
                # POOL-WIDE shard index over the control channel
                assert {o.shard for o in osds} == {1, 2}
                pids = {(await pool.call(i, "worker status"))["pid"]
                        for i in (1, 2)}
                assert len(pids) == 2
                st = await osds[0].status()
                assert st["reactor_shard"] == osds[0].shard
                # per-OSD knob routing: osd.0 and osd.2 share worker
                # shard1, and the handle's config_set must touch ONLY
                # its own daemon (in-process semantics)
                await osds[0].config_set("osd_pg_pipeline_depth", 2)
                assert await osds[0].config_get(
                    "osd_pg_pipeline_depth") == 2
                assert await osds[2].config_get(
                    "osd_pg_pipeline_depth") == 4
                # pool-wide broadcast reaches every hosted OSD
                await pool.config_set("osd_pg_pipeline_depth", 3)
                assert await osds[2].config_get(
                    "osd_pg_pipeline_depth") == 3
        if procs > 0:
            # teardown drained the workers: graceful exit (straggler
            # reap inside the worker ran), not a kill
            assert all(w.proc.returncode == 0 for w in workers)
        return payloads, got
    return run(body(), timeout=180)


def test_proc_cluster_roundtrip_bit_identical_vs_single_loop():
    p1, g1 = _cluster_roundtrip(0)
    p2, g2 = _cluster_roundtrip(2)
    assert g1 == p1                 # single-loop ground truth
    assert g2 == p2                 # process-backed runtime: same bytes
    assert g1 == g2                 # and identical across runtimes


def test_tpu_plugin_pool_on_workers_that_share_one_cpu_device(monkeypatch):
    """`vstart --procs 2` and the hermetic failure_storm drill: no
    XLA_FLAGS, so each worker sees ONE cpu device and worker 2's
    partition slice of it is empty — it serves from that device all the
    same (only a chip belongs to one process)."""
    monkeypatch.delenv("XLA_FLAGS")         # workers inherit at spawn

    async def body():
        from ceph_tpu.tools.cluster_boot import ephemeral_cluster
        payloads = {f"o{i}": bytes([i + 1]) * 65536 for i in range(6)}
        async with ephemeral_cluster(
                6, prefix="proc1dev-",
                reactor_procs=2) as (client, osds, _mon):
            await client.command({
                "prefix": "osd erasure-code-profile set",
                "name": "tpuprof",
                "profile": {"plugin": "tpu", "k": "2", "m": "1"}})
            await client.pool_create("onedev", pg_num=8,
                                     pool_type="erasure",
                                     erasure_code_profile="tpuprof")
            io = client.ioctx("onedev")
            # a worker that refused to serve would leave its primaries'
            # writes to the op timeout, which raises here
            await asyncio.gather(*[io.write_full(oid, data)
                                   for oid, data in payloads.items()])
            for oid, data in payloads.items():
                assert await io.read(oid) == data
    run(body())


# ---------------------------------------------------------------------------
# SIGKILL drill: crash verb -> supervisor reap -> mark-down -> respawn
# ---------------------------------------------------------------------------

def test_worker_crash_reap_markdown_respawn():
    """The dead-shard-host drill end to end: the faultinject `crash`
    verb SIGKILLs a worker (no teardown, no goodbyes), the parent
    supervisor reaps the corpse, the worker's OSDs get marked down by
    the EXISTING reporter-quorum path (surviving peers stop hearing
    heartbeats), and a fresh respawn re-boots the same OSD ids, which
    rejoin and serve I/O."""
    async def body():
        from ceph_tpu.tools.cluster_boot import ephemeral_cluster
        # 4 OSDs over 2 workers: killing shard2 (osd.1 + osd.3) leaves
        # two reporters (osd.0, osd.2) — the mon's reporter quorum
        async with ephemeral_cluster(
                4, prefix="prockill-",
                reactor_procs=2) as (client, osds, mon):
            pool = osds[0].pool
            await client.pool_create("rp", pg_num=8, size=3)
            io = client.ioctx("rp")
            for i in range(6):
                await io.write_full(f"o{i}", b"x" * 4096)
            # config propagation tightens the drill: the grace knob
            # reaches the SURVIVING workers' observers live
            await pool.config_set("osd_heartbeat_grace", 1.0)
            await pool.config_set("osd_heartbeat_interval", 0.25)
            t0 = time.monotonic()
            r = await pool.inject_crash(2)
            assert r["injected"] == "crash" and r["shard"] == 2
            while pool.worker_alive(2):
                assert time.monotonic() - t0 < 15, \
                    "supervisor never reaped the killed worker"
                await asyncio.sleep(0.1)
            # reaped for real: no zombie left behind
            assert pool._worker(2).proc.returncode is not None
            omap = mon.osdmon.osdmap
            while omap.is_up(1) or omap.is_up(3):
                assert time.monotonic() - t0 < 60, \
                    "killed worker's OSDs never marked down"
                await asyncio.sleep(0.2)
            rr = await pool.respawn(2)
            assert {o["whoami"] for o in rr["osds"]} == {1, 3}
            # the fresh process rejoined with the operator's hot knobs
            # REPLAYED, not the defaults — peers run grace 1.0, and a
            # respawn that silently reverted would diverge the cluster
            g = await pool.call(2, {"prefix": "config get",
                                    "key": "osd_heartbeat_grace"})
            assert g["osd_heartbeat_grace"] == 1.0
            while not (omap.is_up(1) and omap.is_up(3)):
                assert time.monotonic() - t0 < 120, \
                    "respawned worker's OSDs never rejoined"
                await asyncio.sleep(0.2)
            # the rejoined cluster serves I/O
            await io.write_full("post", b"y" * 4096)
            assert await io.read("post") == b"y" * 4096
    run(body(), timeout=240)


# ---------------------------------------------------------------------------
# cross-process loopprof attribution (pool-wide shard labels + skew)
# ---------------------------------------------------------------------------

def test_cross_process_profile_stats_use_pool_wide_shard_labels():
    """Each worker accounts its own loop but labels it with the
    POOL-WIDE shard index (reactor.adopt_worker_shard), so the parent's
    merge is keyed shard0/shard1/shard2 — not three pid-local 'loop0's
    — and the cross-process busy skew is computable."""
    async def body():
        from ceph_tpu.tools.cluster_boot import ephemeral_cluster
        from ceph_tpu.utils import loopprof
        async with ephemeral_cluster(
                2, prefix="procprof-",
                reactor_procs=2) as (client, osds, _mon):
            pool = osds[0].pool
            loopprof.install()              # parent shard 0
            try:
                await pool.config_set("profiler_enabled", True)
                await client.pool_create("p", pg_num=4, size=2)
                io = client.ioctx("p")
                for i in range(8):
                    await io.write_full(f"o{i}", b"z" * 8192)
                await asyncio.sleep(0.3)    # every loop turns
                prof = await pool.profile_stats()
                shards = prof["shards"]
                assert {"shard0", "shard1", "shard2"} <= set(shards)
                assert all(d["wall_us"] > 0 for d in shards.values())
                assert all(0 < d["busy_us"] <= d["wall_us"]
                           for d in shards.values())
                assert 0.0 <= prof["shard_busy_skew"] <= 1.0
                # merge helper: same-label parts sum, fractions recompute
                merged = loopprof.merge_shard_stats(
                    {"shard1": {"wall_us": 10.0, "busy_us": 5.0}},
                    {"shard1": {"wall_us": 10.0, "busy_us": 0.0}})
                assert merged["shard1"]["loop_busy_fraction"] == 0.25
                await pool.config_set("profiler_enabled", False)
            finally:
                loopprof.uninstall()
    run(body(), timeout=180)
