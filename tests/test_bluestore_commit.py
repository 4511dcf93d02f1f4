"""BlueStore's commit pipeline against the plain model of its contract
(`benchmarks/reference_bluestore.py`): a transaction is readable from
the moment it is queued and durable from `on_commit`, one commit thread
a store takes what queued while its last sync ran and commits it as one
group, and a kill at any state boundary leaves every committed
transaction whole and a prefix of each collection's."""
from __future__ import annotations

import asyncio
import gc
import os
import shutil
import threading
import time

import pytest

from benchmarks import reference_bluestore as ref
from ceph_tpu.objectstore import bluestore
from ceph_tpu.objectstore.bluestore import AU, INLINE_MAX, BlueStore
from ceph_tpu.objectstore.store import StoreError, Transaction
from ceph_tpu.objectstore.types import CollectionId, Ghobject
from ceph_tpu.utils import tracer
from ceph_tpu.utils.crash import SimulatedCrash

from tests.test_cluster import run

SEEDS = range(20)
KILLS = ("clean_umount", "dropped_while_queued", "fail_before_kv",
         "fail_after_wal", "unsynced_tails_cut")
CID = CollectionId.make_pg(7, 0)
BIG = INLINE_MAX + 3 * AU


def _cid(c: int) -> CollectionId:
    return CollectionId.make_pg(7, c)


def _gh(o: str) -> Ghobject:
    return Ghobject(pool=7, name=o)


def _transaction(txn: list[tuple]) -> Transaction:
    """A reference transaction as the program's."""
    out = Transaction()
    for op in txn:
        kind, cid = op[0], _cid(op[1])
        if kind == "mkcoll":
            out.create_collection(cid)
        elif kind == "touch":
            out.touch(cid, _gh(op[2]))
        elif kind == "write":
            out.write(cid, _gh(op[2]), op[3], op[4])
        elif kind == "truncate":
            out.truncate(cid, _gh(op[2]), op[3])
        elif kind == "setattrs":
            out.setattrs(cid, _gh(op[2]), op[3])
        elif kind == "omap_setkeys":
            out.omap_setkeys(cid, _gh(op[2]), op[3])
        elif kind == "remove":
            out.remove(cid, _gh(op[2]))
        elif kind == "clone":
            out.clone(cid, _gh(op[2]), _gh(op[3]))
    return out


def _model_of(store: BlueStore) -> dict:
    """What every read of the store returns, in the reference's shape."""
    found = {}
    for cid in store.list_collections():
        coll = found[cid.pg_seed] = {}
        assert store.collection_exists(cid)
        for gh in store.collection_list(cid):
            assert store.exists(cid, gh)
            data = bytes(store.read(cid, gh))
            assert store.stat(cid, gh)["size"] == len(data)
            coll[gh.name] = {"data": data,
                             "attrs": store.getattrs(cid, gh),
                             "omap": store.omap_get(cid, gh)}
    return found


class Syncs:
    """`os.fsync` and `os.fdatasync` recorded, ("fdatasync" | "fsync",
    begin | end, thread, file name), and a store's commit thread held
    in them while `gate` is clear. Only such a thread: a sync that
    anything else makes on the event loop (a mon's store) would hold
    the test with it."""

    def __init__(self, monkeypatch):
        self.log: list[tuple] = []
        self.gate = threading.Event()
        self.gate.set()
        self.entered = threading.Event()
        self.sizes: dict[str, int] = {}     # file -> size at its last sync
        self.only: threading.Thread | None = None   # the one thread held
        for name in ("fsync", "fdatasync"):
            monkeypatch.setattr(os, name, self._wrap(name, getattr(os, name)))

    def _wrap(self, name, real):
        def sync(fd):
            path = os.readlink(f"/proc/self/fd/{fd}")
            self.log.append((name, "begin", threading.get_ident(), path))
            me = threading.current_thread()
            if me.name == "bstore-kv-sync" and self.only in (None, me):
                self.entered.set()
                self.gate.wait()
            real(fd)
            if os.path.isfile(path):
                self.sizes[path] = os.path.getsize(path)
            self.log.append((name, "end", threading.get_ident(), path))
        return sync

    def hold(self) -> None:
        self.entered.clear()
        self.gate.clear()

    def release(self) -> None:
        self.gate.set()


@pytest.fixture
def syncs(monkeypatch):
    s = Syncs(monkeypatch)
    yield s
    s.release()


def _store(tmp_path, name="bs") -> BlueStore:
    s = BlueStore(str(tmp_path / name))
    s.mount()
    return s


async def _settled(store: BlueStore) -> None:
    """Wait, without blocking the loop, until the store's thread is idle
    and its callbacks have run."""
    q = store._q
    while q.queued or q.busy or store._done:
        await asyncio.sleep(0.002)
    await asyncio.sleep(0)


def _fresh_model(path: str) -> dict:
    s = BlueStore(path)
    s.mount()
    try:
        return _model_of(s)
    finally:
        s.umount()


# -- the model, and a kill at every state boundary ----------------------------

@pytest.mark.parametrize("kill", KILLS)
@pytest.mark.parametrize("seed", SEEDS)
def test_seeded_sequences_hold_the_contract(tmp_path, syncs, seed, kill):
    txns = ref.make_transactions(seed)
    want = ref.live(txns)
    # where the kill falls: a transaction drawn from the seed, past the
    # collections' own
    at = 3 + (seed * 7 + KILLS.index(kill)) % (len(txns) - 4)
    committed: set[int] = set()
    path = str(tmp_path / "bs")
    copy = str(tmp_path / "killed")

    async def main():
        store = BlueStore(path)
        store.mount()
        for i, txn in enumerate(txns):
            if i == at and kill == "dropped_while_queued":
                syncs.hold()
            if i == at and kill == "fail_before_kv":
                await _settled(store)
                store.fail_before_kv = True
            if i == at and kill == "fail_after_wal":
                await _settled(store)
                store.kv.fail_after_wal = True
            t = _transaction(txn)
            t.register_on_commit(lambda i=i: committed.add(i))
            try:
                store.queue_transaction(t)
            except StoreError as e:
                # a dead store takes no more: the kill has happened
                assert e.code == "EIO" and kill.startswith("fail_")
                assert i > at
                break
            # queued is readable, whatever has committed
            assert _model_of(store) == want[i], f"live state after {i}"
            if i % 3 == 0:
                await asyncio.sleep(0.001)      # let groups form and land
        if kill == "clean_umount":
            store.umount()
            return set(committed)
        if kill == "dropped_while_queued":
            # the thread stands in a sync with transaction `at` onwards
            # prepared or queued: this is what the disk holds, and
            # these the callbacks that had run
            await asyncio.to_thread(syncs.entered.wait, 10)
            shutil.copytree(path, copy)
            at_kill = set(committed)
            syncs.release()
            await _settled(store)
            assert committed == set(range(len(txns)))
            return at_kill
        await _settled(store)
        assert store.stats()["acks_before_sync"] == 0
        shutil.copytree(path, copy)
        if kill == "unsynced_tails_cut":
            # a kill of the machine: what no sync covered is gone
            for f, size in syncs.sizes.items():
                if f.startswith(path + "/") and os.path.exists(f):
                    os.truncate(copy + f[len(path):], size)
        return set(committed)

    committed = run(main())
    found = _fresh_model(path if kill == "clean_umount" else copy)
    assert ref.kill_verdict(txns, committed, found) == []
    if kill in ("clean_umount", "unsynced_tails_cut"):
        # everything was committed before the kill: it is all there
        assert committed == set(range(len(txns)))
        assert found == want[-1]
    if kill == "dropped_while_queued":
        assert committed < set(range(len(txns)))
    if kill.startswith("fail_"):
        # the group that failed, and all behind it, never called back
        # (a transaction that changes nothing syncs nothing and may
        # pass the KV's hook)
        dead = min(set(range(len(txns))) - committed)
        assert dead >= at and committed == set(range(dead))
        # before the KV: data landed, metadata did not. After the log's
        # sync: the group is replayed whole at the mount
        assert found == want[dead - 1] if kill == "fail_before_kv" \
            else found in want[dead:]


@pytest.mark.parametrize("seed", [101, 102, 103])
def test_reads_and_commits_interleave_under_a_short_switch_interval(
        tmp_path, seed):
    """The two threads share the allocator, the overlay of uncommitted
    state and the KV's tables: with the interpreter switching threads
    every 10 us, every read between two queues still returns the model
    (a lost overlay entry or a table changed under an iteration would
    not), and the fresh mount holds it all."""
    import sys

    txns = ref.make_transactions(seed, n=90, collections=2, objects=3)
    want = ref.live(txns)
    path = str(tmp_path / "bs")

    async def main():
        store = BlueStore(path)
        store.mount()
        deadline = time.monotonic() + 60
        for i, txn in enumerate(txns):
            store.queue_transaction(_transaction(txn))
            assert _model_of(store) == want[i], f"live state after {i}"
            if i % 5 == 0:
                await asyncio.sleep(0)
            assert time.monotonic() < deadline
        groups = store.stats()["kv_syncs"]
        store.umount()
        return groups

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        groups = run(main())
    finally:
        sys.setswitchinterval(old)
    assert 1 <= groups <= len(txns)
    assert _fresh_model(path) == want[-1]


def test_the_reference_refuses_what_the_contract_refuses():
    state = ref.apply({}, [("mkcoll", 0), ("touch", 0, "a")])
    with pytest.raises(KeyError):
        ref.apply(state, [("touch", 0, "b"), ("remove", 0, "nope")])
    assert state == {0: {"a": {"data": b"", "attrs": {}, "omap": {}}}}
    txns = [[("mkcoll", 0)], [("touch", 0, "a")], [("mkcoll", 1)],
            [("write", 0, "a", 2, b"xy")], [("touch", 1, "b")]]
    full = ref.live(txns)[-1]
    assert full[0]["a"]["data"] == b"\x00\x00xy"
    # committed up to 1: collection 0 may lack the write, never `a`
    assert ref.kill_verdict(txns, {0, 1}, {0: {"a": full[0]["a"]}}) == []
    assert ref.kill_verdict(
        txns, {0, 1}, {0: {"a": {"data": b"", "attrs": {}, "omap": {}}},
                       1: {}}) == []
    assert ref.kill_verdict(txns, {0, 1}, {0: {}}) != []
    # a later transaction of ANOTHER collection may be there without it
    assert ref.kill_verdict(txns, {0}, {0: {}, 1: full[1]}) == []
    # but not a later one of the same collection without an earlier
    assert ref.kill_verdict(
        txns, set(), {0: {"a": {"data": b"xy", "attrs": {}, "omap": {}}}}
    ) != []


# -- the pipeline -----------------------------------------------------------------

def _write(name: str, size: int = BIG, on_commit=None) -> Transaction:
    t = Transaction().write(CID, _gh(name), 0, os.urandom(size))
    if on_commit is not None:
        t.register_on_commit(on_commit)
    return t


@pytest.mark.parametrize("n", [1, 5, 24])
def test_what_queues_during_a_sync_commits_as_one_group(tmp_path, syncs, n):
    fired = []

    async def main():
        store = _store(tmp_path)
        store.queue_transaction(Transaction().create_collection(CID))
        await _settled(store)
        base = store.stats()
        syncs.hold()
        store.queue_transaction(_write("first", on_commit=lambda:
                                       fired.append("first")))
        await asyncio.to_thread(syncs.entered.wait, 10)
        for i in range(n):      # the thread stands in the first's sync
            store.queue_transaction(_write(
                f"o{i}", on_commit=lambda i=i: fired.append(i)))
        assert fired == []
        syncs.release()
        await _settled(store)
        after = store.stats()
        assert after["txcs"] - base["txcs"] == n + 1
        assert after["kv_syncs"] - base["kv_syncs"] == 2    # not n + 1
        assert after["block_syncs"] - base["block_syncs"] == 2
        assert after["kv_fsyncs"] - base["kv_fsyncs"] == 2
        assert after["acks_before_sync"] == 0
        assert fired == ["first", *range(n)]                # queue order
        store.umount()

    run(main())


def test_no_commit_before_the_syncs_that_cover_it(tmp_path, syncs):
    """Each `on_commit` comes after a sync of the block file (where the
    transaction wrote extents) and after a sync of the KV log, both
    begun after the transaction was queued."""
    async def main():
        store = _store(tmp_path)
        store.queue_transaction(Transaction().create_collection(CID))
        await _settled(store)
        marks = {}
        for i, size in enumerate([BIG, 10, BIG, INLINE_MAX, BIG + AU]):
            def done(i=i):
                marks[i] = (marks[i], len(syncs.log))
            store.queue_transaction(_write(f"o{i}", size, done))
            marks[i] = len(syncs.log)
            if i % 2:
                await _settled(store)
        await _settled(store)
        for i, size in enumerate([BIG, 10, BIG, INLINE_MAX, BIG + AU]):
            queued_at, fired_at = marks[i]
            between = syncs.log[queued_at:fired_at]
            ends = [(name, os.path.basename(p))
                    for name, edge, _t, p in between if edge == "end"]
            # a sync that BEGAN before the transaction was queued does
            # not cover it: only whole syncs inside the stretch count
            begun = [(name, os.path.basename(p))
                     for name, edge, _t, p in between if edge == "begin"]
            whole = [s for s in ends if s in begun]
            assert ("fsync", "wal.log") in whole, (i, between)
            if size > INLINE_MAX:
                assert ("fdatasync", "block") in whole, (i, between)
                assert whole.index(("fdatasync", "block")) \
                    < len(whole) - 1 - whole[::-1].index(
                        ("fsync", "wal.log"))
        assert store.stats()["acks_before_sync"] == 0
        store.umount()

    run(main())


def test_a_read_between_queue_and_commit_returns_the_queued(tmp_path, syncs):
    async def main():
        store = _store(tmp_path)
        store.queue_transaction(Transaction().create_collection(CID))
        store.queue_transaction(
            Transaction().write(CID, _gh("old"), 0, b"x" * BIG)
            .omap_setkeys(CID, _gh("old"), {"k": b"v"}))
        await _settled(store)
        syncs.hold()
        big, small = os.urandom(BIG), os.urandom(100)
        applied, fired = [], []
        t = Transaction().write(CID, _gh("a"), 0, big) \
            .setattrs(CID, _gh("a"), {"n": b"1"}) \
            .omap_setkeys(CID, _gh("a"), {"k1": b"v1"})
        t.register_on_applied(lambda: applied.append(1))
        t.register_on_commit(lambda: fired.append(1))
        store.queue_transaction(t)
        assert applied == [1] and fired == []      # readable, not durable
        store.queue_transaction(_write("b", 1))
        store.queue_transaction(
            Transaction().write(CID, _gh("b"), 0, small)
            .clone(CID, _gh("a"), _gh("c")).remove(CID, _gh("old")))
        other = CollectionId.make_pg(7, 9)
        store.queue_transaction(Transaction().create_collection(other))
        assert fired == []
        assert store.read(CID, _gh("a")) == big
        assert store.read(CID, _gh("a"), 5, 10) == big[5:15]
        assert store.stat(CID, _gh("a")) == {"size": BIG}
        assert store.getattr(CID, _gh("a"), "n") == b"1"
        assert store.omap_get(CID, _gh("a")) == {"k1": b"v1"}
        assert store.omap_get_values(CID, _gh("c"), ["k1", "zz"]) \
            == {"k1": b"v1"}
        assert store.read(CID, _gh("b")) == small
        assert store.read(CID, _gh("c")) == big
        assert not store.exists(CID, _gh("old"))
        assert [g.name for g in store.collection_list(CID)] == ["a", "b", "c"]
        assert store.collection_exists(other)
        assert other in store.list_collections()
        # none of it is in the KV yet
        assert store.kv.get(bluestore.P_ONODE,
                            bluestore._onode_key(CID, _gh("a"))) is None
        syncs.release()
        await _settled(store)
        assert fired == [1]
        assert not store._pend_onodes and not store._pend_omap \
            and not store._pend_colls
        assert store.read(CID, _gh("c")) == big     # from the KV now
        assert [g.name for g in store.collection_list(CID)] == ["a", "b", "c"]
        store.umount()

    run(main())


@pytest.mark.parametrize("hook", ["fail_before_kv", "fail_after_wal"])
def test_a_failed_group_fires_nothing_and_restores_the_allocator(
        tmp_path, syncs, hook):
    fired = []

    async def main():
        store = _store(tmp_path)
        store.queue_transaction(Transaction().create_collection(CID))
        store.queue_transaction(_write("keep"))
        await _settled(store)
        used = sum(store.alloc.bits)
        setattr(store if hook == "fail_before_kv" else store.kv, hook, True)
        syncs.hold()
        for i in range(3):
            store.queue_transaction(_write(
                f"lost{i}", on_commit=lambda: fired.append(1)))
        store.queue_transaction(Transaction().remove(CID, _gh("keep")))
        assert sum(store.alloc.bits) > used
        syncs.release()
        await _settled(store)
        assert fired == []
        assert sum(store.alloc.bits) == used    # theirs back, `keep`'s kept
        with pytest.raises(StoreError) as ei:
            store.queue_transaction(_write("more"))
        assert ei.value.code == "EIO"
        late = []
        store.flush_commit(lambda: late.append(1))
        assert late == []                       # never: the store is dead
        store.umount()

    run(main())


def test_umount_drains_the_queue(tmp_path, syncs):
    fired = []

    async def main():
        store = _store(tmp_path)
        store.queue_transaction(Transaction().create_collection(CID))
        for i in range(8):
            store.queue_transaction(_write(
                f"o{i}", on_commit=lambda i=i: fired.append(i)))
        store.umount()
        assert fired == list(range(8))
        assert store._thread is None

    run(main())
    s = _store(tmp_path)
    assert len(s.collection_list(CID)) == 8
    s.umount()


def test_callbacks_run_on_the_loop_and_syncs_beside_it(tmp_path, syncs):
    where = []

    async def main():
        store = _store(tmp_path)
        store.queue_transaction(Transaction().create_collection(CID))
        store.queue_transaction(_write(
            "a", on_commit=lambda: where.append(threading.get_ident())))
        await _settled(store)
        store.umount()
        return threading.get_ident()

    loop_thread = run(main())
    assert where == [loop_thread]
    sync_threads = {t for _n, _e, t, p in syncs.log
                    if os.path.basename(p) in ("block", "wal.log")}
    assert sync_threads and loop_thread not in sync_threads


def test_without_a_loop_the_call_is_the_commit(tmp_path, syncs):
    """The tools and the plain tests: callbacks and the commit's
    exception arrive before `queue_transaction` returns."""
    store = _store(tmp_path)
    order = []
    t = Transaction().create_collection(CID)
    t.register_on_applied(lambda: order.append("applied"))
    t.register_on_commit(lambda: order.append("commit"))
    store.queue_transaction(t)
    assert order == ["applied", "commit"]
    assert store.stats()["kv_syncs"] == 1
    assert any(e == ("fsync", "end") for e in
               [(n, edge) for n, edge, _t, _p in syncs.log])
    flushed = []
    store.flush_commit(lambda: flushed.append(1))
    assert flushed == [1]
    store.fail_before_kv = True
    with pytest.raises(SimulatedCrash):
        store.queue_transaction(_write("x", on_commit=lambda:
                                       order.append("never")))
    assert order == ["applied", "commit"]
    store.umount()


def test_flush_commit_rides_the_last_queued(tmp_path, syncs):
    async def main():
        store = _store(tmp_path)
        store.queue_transaction(Transaction().create_collection(CID))
        await _settled(store)
        order = []
        store.flush_commit(lambda: order.append("idle"))
        assert order == ["idle"]
        syncs.hold()
        store.queue_transaction(_write("a", on_commit=lambda:
                                       order.append("a")))
        store.queue_transaction(_write("b", on_commit=lambda:
                                       order.append("b")))
        store.flush_commit(lambda: order.append("barrier"))
        assert order == ["idle"]
        syncs.release()
        await _settled(store)
        assert order == ["idle", "a", "b", "barrier"]
        store.umount()

    run(main())


def test_a_store_dropped_without_umount_leaves_no_thread(tmp_path):
    def mine():
        return [t for t in threading.enumerate()
                if t.name == "bstore-kv-sync"]

    before = len(mine())
    store = _store(tmp_path)
    store.queue_transaction(Transaction().create_collection(CID))
    assert len(mine()) == before + 1
    del store
    gc.collect()
    deadline = time.monotonic() + 10
    while len(mine()) > before and time.monotonic() < deadline:
        time.sleep(0.01)
    assert len(mine()) == before


def test_flush_leaves_nothing_for_a_second_mount_to_race(tmp_path, syncs):
    """The benchmark's remount check: a daemon is stopped with `umount`
    shadowed, then a second store mounts the directory while the first
    still lives."""
    async def main():
        first = _store(tmp_path)
        first.queue_transaction(Transaction().create_collection(CID))
        data = os.urandom(BIG)
        first.queue_transaction(Transaction().write(CID, _gh("a"), 0, data))
        first.flush()
        n = len(syncs.log)
        second = BlueStore(first.path)
        second.mount()
        assert second.read(CID, _gh("a")) == data
        await asyncio.sleep(0.05)
        assert len(syncs.log) == n          # the first wrote nothing more
        second.umount()
        first.umount()

    run(main())


def test_the_pipeline_is_traced(tmp_path):
    async def main():
        store = _store(tmp_path)
        store.queue_transaction(Transaction().create_collection(CID))
        await _settled(store)
        tracer.enable()
        try:
            cursor = tracer.collector().last_seq()
            store.queue_transaction(_write("a"))
            store.queue_transaction(_write("b", 10))
            await _settled(store)
            spans = [s for s in tracer.collector().spans()
                     if s["seq"] > cursor]
        finally:
            tracer.disable()
        store.umount()
        return spans

    spans = run(main())
    txcs = [s for s in spans if s["name"] == "bstore_txc"]
    groups = [s for s in spans if s["name"] == "bstore_kv_sync"]
    assert len(txcs) == 2 and 1 <= len(groups) <= 2
    for s in txcs:
        assert set(s["tags"]) >= {"prepare_us", "queued_us", "block_sync_us",
                                  "kv_submit_us", "deliver_us", "ops",
                                  "bytes", "group", "ran_ahead"}
        assert s["tags"]["ran_ahead"] is False
        legs = sum(s["tags"][k] for k in ("prepare_us", "queued_us",
                                          "block_sync_us", "kv_submit_us",
                                          "deliver_us"))
        assert legs == pytest.approx(s["duration_us"], rel=0.05, abs=50)
    assert sorted(s["tags"]["bytes"] for s in txcs) == [0, BIG + AU - BIG % AU
                                                        if BIG % AU else BIG]
    for g in groups:
        assert set(g["tags"]) >= {"txcs", "block_synced", "kv_fsyncs",
                                  "block_bytes", "kv_bytes",
                                  "freelist_bytes", "group"}
        assert g["tags"]["kv_fsyncs"] >= 1 and g["tags"]["kv_bytes"] > 0
    assert sum(g["tags"]["txcs"] for g in groups) == 2
    assert {s["tags"]["group"] for s in txcs} \
        == {g["tags"]["group"] for g in groups}
    # the prepare is what `store_commit` measures: no sync inside it
    commits = [s for s in spans if s["name"] == "store_commit"]
    assert len(commits) == 2
