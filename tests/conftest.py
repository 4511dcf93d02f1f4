"""Test harness config: run JAX on a virtual 8-device CPU mesh.

Mirrors the reference's approach of exercising multi-daemon behavior on one
host (qa/standalone/ceph-helpers.sh): we exercise multi-chip sharding on one
host via XLA's virtual CPU devices. Must run before jax initializes a backend.

Tests must be deterministic and accelerator-independent: JAX_PLATFORMS=cpu is
set here for this process and for every subprocess a test spawns.
"""
import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()

# ---------------------------------------------------------------------------
# asyncio task-leak gate: any test that leaves an event-loop task pending at
# loop teardown ("Task was destroyed but it is pending!" — the BENCH_r05 tail
# spam) FAILS instead of spamming stderr. asyncio reports destroyed-pending
# tasks through the loop exception handler, which logs to the 'asyncio'
# logger when the task object is garbage-collected; the autouse fixture
# forces that collection inside the owning test via gc.collect().
# ---------------------------------------------------------------------------
import gc        # noqa: E402
import logging   # noqa: E402

import pytest    # noqa: E402


class _AsyncioLeakHandler(logging.Handler):
    def __init__(self):
        super().__init__(level=logging.ERROR)
        self.leaks: list[str] = []

    def emit(self, record):
        msg = record.getMessage()
        if "was destroyed but it is pending" in msg:
            self.leaks.append(msg)


_leak_handler = _AsyncioLeakHandler()
logging.getLogger("asyncio").addHandler(_leak_handler)


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: excluded from the tier-1 `-m 'not slow'` run")
    config.addinivalue_line(
        "markers",
        "interleave: schedule-interleaving seed sweeps (the qa tier)")


def pytest_collection_modifyitems(config, items):
    """tests/benchmarks/test_store_direct.py runs a case for every cell
    of BENCHMARK.json and asserts that exactly one of the stores' two
    shares (`store_write_direct_pct`, `store_read_direct_pct`) lists the
    cell (`len(mine) == 1`), and for a write cell that the share is at
    least 30. Those lists are accepted entries, which a `model_config`
    PR may not extend, and BlueStore keeps no body by reference (the
    share would read 0.0): the case the file generates for
    `rb4m_bluestore_write` (PR 45) cannot pass whatever the program
    does. tests/benchmarks/test_bluestore_cell.py holds what the case
    meant for this cell (a correct tiny traced run, neither share on
    its line). The same holds for `rb64k_write` (PR 49): its shards are
    kept by reference as `rb4m_write`'s are, but the list that would
    say so is not this PR's to extend; tests/benchmarks/
    test_rb64k_cell.py holds what the case meant. And for
    `rb64k_bluestore_write` (PR 53), for both reasons at once;
    tests/benchmarks/test_rb64k_bluestore_cell.py holds what the case
    meant. The next `benchmark` PR drops the per-cell `len(mine) == 1`
    there and takes all three marks out too.

    (The three marks that stood here before, for cases of
    test_loop_account.py, test_store_direct.py's fast-read case and the
    `msgr_ctrl_rode_pct` cases, were stale: PR 44 repaired the files and
    their thirteen cases passed as `xpassed`.)"""
    case = ("test_store_direct.py::test_tiny_traced_run_reports_the_"
            "stores_share[%s]")
    why = ("wants exactly one of the stores' two shares to list every "
           "cell, from accepted entries this PR may not extend%s. "
           "Superseded by %s")
    marks = {
        "rb4m_bluestore_write": why % (
            "; BlueStore keeps no body by reference",
            "test_bluestore_cell.py (PR 45)"),
        "rb64k_write": why % ("", "test_rb64k_cell.py (PR 49)"),
        "rb64k_bluestore_write": why % (
            "; BlueStore keeps no body by reference",
            "test_rb64k_bluestore_cell.py (PR 53)")}
    for item in items:
        for cell, reason in marks.items():
            if item.nodeid.endswith(case % cell):
                item.add_marker(pytest.mark.xfail(reason=reason,
                                                  strict=True))


@pytest.fixture(autouse=True)
def _no_pending_task_leaks():
    """Fail any test that destroys pending event-loop tasks.

    Young-generation collection only: a task leaked by THIS test is
    gen0/gen1 (created minutes ago at most), while a full gc.collect()
    walks the whole heap and costs hundreds of ms by late suite —
    measured ~20% of the tier-1 budget. A leaked task promoted to gen2
    under heavy allocation still surfaces at a later test's collection
    (slightly misattributed, but never silent).
    """
    start = len(_leak_handler.leaks)
    yield
    gc.collect(1)
    fresh = _leak_handler.leaks[start:]
    assert not fresh, (
        f"{len(fresh)} asyncio task(s) destroyed while pending — a "
        f"daemon/messenger teardown failed to cancel-and-await them:\n"
        + "\n".join(fresh[:10]))
    # the loop sampling profiler must unwind with the test's loop: a
    # still-armed loop means an uninstall() was skipped, and the task
    # factory it installed would bleed spawn-site recording (and a
    # daemon sampler thread) into every later test
    from ceph_tpu.utils import loopprof
    live = loopprof.installed_loops()
    assert not live, (
        f"loop profiler still armed on {len(live)} loop(s) after the "
        f"test — loopprof.uninstall() (or profiler_enabled=false) "
        f"missing from teardown")
    # foreign-loop call_soon gate: while the sanitizer was armed, any
    # loop.call_soon driven from a thread that doesn't own the loop was
    # recorded — teardown code that swallowed asyncio's debug-mode
    # RuntimeError (or raced loop close) still fails HERE. Drained per
    # test so a stray is attributed to the test that caused it.
    from ceph_tpu.utils import sanitizer
    strays = sanitizer.take_foreign_call_soon()
    assert not strays, (
        f"{len(strays)} foreign-thread call_soon event(s) recorded by "
        f"the sanitizer — use call_soon_threadsafe (or run_on) to cross "
        f"loops:\n" + "\n".join(
            f"  {s['callback']} -> {s['loop']}" for s in strays[:10]))


@pytest.fixture(autouse=True)
def _offload_defaults_restored():
    """A daemon's `config set ec_offload_*` also moves the offload
    module's defaults, which every later cluster of the process
    inherits. A test that serves a cell whose configuration sets one
    (`rb4m_scrub_seqread`: `ec_offload_crc_device`; the accepted tests
    under tests/benchmarks/ serve every cell of BENCHMARK.json) must not
    hand it to the next file on its worker, where `test_chip_smoke`
    serves at the defaults."""
    from ceph_tpu.offload import service
    kept = dict(service._DEFAULTS)
    yield
    service._DEFAULTS.update(kept)
