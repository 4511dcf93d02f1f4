"""chip_smoke.py and what it rests on, as far as a CPU can show it: the
entry refuses any platform but the TPU, the phases pass their
comparisons at a tiny size (so chip time is not spent on typos), device
selection is loud, and the compile cache lands where it is told.
"""
from __future__ import annotations

import asyncio
import os
import subprocess
import sys

import pytest

import chip_smoke
from ceph_tpu.offload import service as offload_service

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(code_or_script: list[str], env_changes: dict) -> subprocess.CompletedProcess:
    env = dict(os.environ)
    for key, value in env_changes.items():
        if value is None:
            env.pop(key, None)
        else:
            env[key] = value
    return subprocess.run([sys.executable, *code_or_script], cwd=REPO,
                          env=env, capture_output=True, text=True,
                          timeout=120)


def test_entry_refuses_the_cpu_and_names_it():
    proc = _run(["chip_smoke.py"], {"JAX_PLATFORMS": "cpu"})
    assert proc.returncode != 0
    assert "JAX_PLATFORMS='cpu'" in proc.stderr
    assert '"ok"' not in proc.stdout


def test_phases_pass_their_comparisons_small():
    kernels = chip_smoke.phase_kernels(3, batch=2, chunk=4096, crc_blocks=64)
    assert kernels["encode"]["shape"] == [2, 8, 4096]
    n, size = 8, 1 << 20
    served = asyncio.run(chip_smoke.phase_served(
        3, n_objects=n, object_size=size, in_flight=4, pg_num=8,
        sampled=2, degraded_reads=4))
    chip_smoke.check_served(served, "cpu", min_device_bytes=n * size)
    assert served["write"]["ops"] == served["read"]["ops"] == n
    assert len(served["stopped_osds"]) == chip_smoke.M
    # the same counters, judged for the wrong platform, must not pass
    with pytest.raises(AssertionError, match="tpu"):
        chip_smoke.check_served(served, "tpu", min_device_bytes=n * size)


def test_check_served_fails_on_host_fallback():
    ok = {"offload": {"batches": 4, "fallback_ops": 0, "breaker_trips": 0,
                      "device_failovers": 0, "degraded": False,
                      "kernel_gb_s": {"enc": 1.0, "dec": 1.0}},
          "devices": {"tpu:0": {"ops": 6, "bytes": 100}},
          "native_frames": True}
    chip_smoke.check_served(ok, "tpu", min_device_bytes=100)
    bad = dict(ok, offload=dict(ok["offload"], fallback_ops=2,
                                breaker_trips=1))
    with pytest.raises(AssertionError, match="fallback_ops=2"):
        chip_smoke.check_served(bad, "tpu", min_device_bytes=100)
    with pytest.raises(AssertionError, match="expected"):
        chip_smoke.check_served(ok, "tpu", min_device_bytes=101)
    # anything on the host lane: a write's checksums ride its encode
    bad = dict(ok, devices=dict(ok["devices"], host={"ops": 5, "bytes": 60}))
    with pytest.raises(AssertionError, match="host lane holds"):
        chip_smoke.check_served(bad, "tpu", min_device_bytes=100)


def test_markdown_gate_fails_when_the_flight_ring_overflowed():
    from ceph_tpu.utils import flight
    capacity = flight.status()["capacity"]
    cursor = flight.last_seq()
    flight.record("osd_markdown", "osd.7")
    flight.record("config_change", "osd.1")
    assert chip_smoke._markdowns_since(cursor) == {"osd.7"}
    try:
        flight.configure(capacity=8)
        for _ in range(8):          # pushes the mark-down out of the ring
            flight.record("config_change", "osd.1")
        with pytest.raises(AssertionError, match="overflowed"):
            chip_smoke._markdowns_since(cursor)
    finally:
        flight.configure(capacity=capacity)


# -- device selection is loud -------------------------------------------------

def test_topology_raises_when_jax_cannot_enumerate(monkeypatch):
    import jax

    def no_backend():
        raise RuntimeError("Unable to initialize backend 'tpu'")
    monkeypatch.setattr(jax, "devices", no_backend)
    with pytest.raises(RuntimeError, match="Unable to initialize backend"):
        offload_service._Topology().device_states(0)


def test_worker_without_a_chip_of_its_own_raises(monkeypatch):
    import jax

    class Chip:
        platform, id = "tpu", 0
    monkeypatch.setattr(jax, "devices", lambda: [Chip()])
    monkeypatch.setenv("CEPH_TPU_OFFLOAD_DEVICE_PARTITION", "1/2")
    with pytest.raises(RuntimeError, match="no chip of its own"):
        offload_service._Topology().device_states(0)
    monkeypatch.setenv("CEPH_TPU_OFFLOAD_DEVICE_PARTITION", "0/2")
    assert [s.label for s in
            offload_service._Topology().device_states(0)] == ["tpu:0"]


def test_cpu_workers_share_the_host_device(monkeypatch):
    """`vstart --procs 2` and the failure_storm drill on a one-device
    host: the cpu device is every process's own."""
    import jax
    n = len(jax.devices())
    monkeypatch.setenv("CEPH_TPU_OFFLOAD_DEVICE_PARTITION", f"{n}/{n + 1}")
    assert [s.label for s in
            offload_service._Topology().device_states(0)] == ["cpu:0"]


def test_batch_that_cannot_be_routed_fails_its_riders_at_once(monkeypatch):
    import jax
    import numpy as np

    from ceph_tpu.ec.registry import ErasureCodePluginRegistry

    code = ErasureCodePluginRegistry.instance().factory(
        "tpu", {"plugin": "tpu", "k": "2", "m": "1"})

    def no_backend():
        raise RuntimeError("Unable to initialize backend 'tpu'")

    async def body():
        # a fresh service: its topology is not built until the first route
        svc = offload_service.OffloadService(asyncio.get_running_loop())
        monkeypatch.setattr(jax, "devices", no_backend)
        stripes = np.zeros((1, 2, 4096), dtype=np.uint8)
        with pytest.raises(RuntimeError, match="Unable to initialize"):
            await asyncio.wait_for(svc.encode(code, stripes), 10)
        await svc.drain()
    asyncio.run(body())


# -- compile cache placement --------------------------------------------------

_PRINT_CACHE_DIR = ("import ceph_tpu, jax; "
                    "print(jax.config.jax_compilation_cache_dir)")


def test_compile_cache_follows_the_environment(tmp_path):
    proc = _run(["-c", _PRINT_CACHE_DIR],
                {"JAX_COMPILATION_CACHE_DIR": str(tmp_path)})
    assert proc.stdout.strip() == str(tmp_path), proc.stderr


def test_compile_cache_default_is_one_fixed_path_in_the_checkout():
    seen = {_run(["-c", _PRINT_CACHE_DIR],
                 {"JAX_COMPILATION_CACHE_DIR": None}).stdout.strip()
            for _ in range(2)}
    assert seen == {os.path.join(REPO, ".jax_cache")}
    # jax imported first (as an entry point may): same place
    proc = _run(["-c", "import jax, ceph_tpu; "
                       "print(jax.config.jax_compilation_cache_dir)"],
                {"JAX_COMPILATION_CACHE_DIR": None})
    assert proc.stdout.strip() == os.path.join(REPO, ".jax_cache")


# -- 3.12's eager gather: drain()/flush() over already-finished tasks ---------

def _finished_but_still_tracked(track) -> "asyncio.Future":
    """Track a task and return once it is DONE while its done-callbacks
    (the set.discard that untracks it) are still queued on the loop —
    the state in which `while tasks: await gather(*tasks)` never
    suspends and so never lets them run."""
    woke = asyncio.Event()

    async def finish():
        woke.set()                # our wake-up is queued first ...
    track(asyncio.get_running_loop().create_task(finish()))
    return woke.wait()            # ... so we resume before the discard


def _fail_if_gather_spins(monkeypatch) -> None:
    """A spin that never suspends cannot be timed out from inside the
    loop; count the gathers instead and fail fast."""
    real, calls = asyncio.gather, [0]

    def counted(*aws, **kw):
        calls[0] += 1
        assert calls[0] < 1000, "gather loop spins without suspending"
        return real(*aws, **kw)
    monkeypatch.setattr(asyncio, "gather", counted)


def test_offload_drain_returns_when_every_task_is_already_done(monkeypatch):
    _fail_if_gather_spins(monkeypatch)

    async def body():
        svc = offload_service.get_service()
        await _finished_but_still_tracked(svc._track)
        assert svc._tasks and all(t.done() for t in svc._tasks)
        await svc.drain()
        assert not svc._tasks
    asyncio.run(body())


def test_aio_flush_returns_when_every_op_is_already_done(monkeypatch):
    from ceph_tpu.rados.aio import AioDispatcher
    _fail_if_gather_spins(monkeypatch)

    async def body():
        disp = AioDispatcher()
        woke = asyncio.Event()

        async def op():
            woke.set()
            return 1
        comp = disp.submit(op())
        await woke.wait()
        assert disp.inflight == 1 and comp.is_complete()
        await disp.flush()
        assert disp.inflight == 0
    asyncio.run(body())
