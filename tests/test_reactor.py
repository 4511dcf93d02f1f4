"""Reactor registry and cross-thread primitive tests: the one-slot
placement registry a worker process registers itself in
(`adopt_worker_shard`), and the AdjustableSemaphore/Throttle audit
against the threads that remain beside a loop (executor pools, admin
sockets). The process pool itself is tests/test_reactor_procs.py. Every
test here runs under the conftest pending-task leak gate."""
import asyncio
import threading

from ceph_tpu.utils import reactor
from ceph_tpu.utils.throttle import AdjustableSemaphore, Throttle


def run(coro, timeout=120):
    return asyncio.run(asyncio.wait_for(coro, timeout))


# ---------------------------------------------------------------------------
# placement registry
# ---------------------------------------------------------------------------

def test_shard_placement_and_registry():
    async def adopt(index):
        loop = asyncio.get_running_loop()
        # unpooled loops answer None (tests/tools keep their own world)
        assert reactor.pool_for(loop) is None
        assert reactor.shard_index_of(loop) is None
        assert reactor.shard_label(loop) is None
        assert reactor.current_pool() is None
        reactor.adopt_worker_shard(index, "t-adopt")
        stub = reactor.pool_for(loop)
        assert (stub.name, stub.index) == ("t-adopt", index)
        assert reactor.current_pool() is stub
        assert reactor.shard_index_of(loop) == index
        assert reactor.shard_label(loop) == f"shard{index}"
        # one slot per loop: a second registration replaces the first
        reactor.adopt_worker_shard(index + 1, "t-adopt")
        assert reactor.shard_label(loop) == f"shard{index + 1}"
        # the answers do not depend on the asking thread
        seen = []
        t = threading.Thread(
            target=lambda: seen.append(reactor.shard_index_of(loop)))
        t.start()
        t.join(10)
        assert seen == [index + 1]
        return loop
    first = run(adopt(2))
    assert first.is_closed()
    # a closed loop's entry is dropped at the next registration
    second = run(adopt(5))
    assert first not in reactor._by_loop
    assert reactor.pool_for(first) is None
    assert reactor.shard_index_of(second) == 6


# ---------------------------------------------------------------------------
# AdjustableSemaphore / Throttle cross-thread audit
# ---------------------------------------------------------------------------

def _on_thread(fn) -> None:
    """Run `fn()` on a plain thread of its own and wait for it."""
    t = threading.Thread(target=fn)
    t.start()
    t.join(10)
    assert not t.is_alive()


def test_adjustable_semaphore_cross_shard_release_and_resize():
    """Acquire on the loop, release on another thread: the release must
    marshal to the owning loop (waiters wake there), never corrupt
    `_value`."""
    async def body():
        sem = AdjustableSemaphore(1)
        await sem.acquire()              # binds to this loop
        woke = asyncio.Event()

        async def waiter():
            await sem.acquire()
            woke.set()
        wt = asyncio.get_running_loop().create_task(waiter())
        await asyncio.sleep(0.05)
        assert not woke.is_set()

        _on_thread(sem.release)          # from a foreign thread
        await asyncio.wait_for(woke.wait(), 5)
        await wt
        sem.release()
        assert sem._value == 1 and sem._debt == 0

        _on_thread(lambda: sem.resize(3))
        await asyncio.sleep(0.05)        # marshalled resize lands
        assert sem.limit == 3
        assert sem._value == 3           # grew by exactly 2
    run(body())


def test_throttle_cross_thread_budget_consistency():
    """The byte-budget Throttle is driven from the loop, the executor's
    threads and the admin thread: hammer get/put from 4 threads and the
    count must return to exactly zero — no lost or doubled units."""
    th = Throttle("xthread", 64)
    errs = []

    def worker():
        try:
            for _ in range(400):
                assert th.get(3, timeout=10)
                th.put(3)
        except Exception as e:   # pragma: no cover - failure reporting
            errs.append(e)
    threads = [threading.Thread(target=worker) for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(30)
    assert not errs
    assert th.current == 0
