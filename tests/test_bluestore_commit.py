"""BlueStore's commit pipeline against the plain model of its contract
(`benchmarks/reference_bluestore.py`): a transaction is readable from
the moment it is queued and durable from `on_commit`, one commit thread
a store takes what queued while its last sync ran and commits it as one
group, and a kill at any state boundary leaves every committed
transaction whole and a prefix of each collection's."""
from __future__ import annotations

import asyncio
import errno
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
         "fail_after_wal", "unsynced_tails_cut", "fail_at_block_sync")
CID = CollectionId.make_pg(7, 0)
BIG = INLINE_MAX + 3 * AU
SHARD = 512 * 1024      # an EC shard of the benchmark's 4 MiB objects
#: under the line (`prefer_deferred_size`, INLINE_MAX by default; the
#: rule is strict): a deferred write, acknowledged from the KV's sync
#: and landed on the block file behind it
UNDER = INLINE_MAX - AU


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
    """`os.fsync`, `os.fdatasync` and `os.pwrite` recorded, ("fdatasync"
    | "fsync" | "pwrite", begin | end, thread, file name), and a store's
    commit thread held in the syncs while `gate` is clear. Only such a
    thread: a sync that anything else makes on the event loop (a mon's
    store) would hold the test with it. `fail[name]` is raised, once,
    by the next `name` on a block file."""

    def __init__(self, monkeypatch):
        self.log: list[tuple] = []
        self.gate = threading.Event()
        self.gate.set()
        self.entered = threading.Event()
        self.sizes: dict[str, int] = {}     # file -> size at its last sync
        self.only: threading.Thread | None = None   # the one thread held
        self.fail: dict[str, OSError] = {}
        for name in ("fsync", "fdatasync", "pwrite"):
            monkeypatch.setattr(os, name, self._wrap(name, getattr(os, name)))

    def _wrap(self, name, real):
        is_sync = name != "pwrite"

        def call(fd, *args):
            path = os.readlink(f"/proc/self/fd/{fd}")
            self.log.append((name, "begin", threading.get_ident(), path))
            me = threading.current_thread()
            if is_sync and me.name == "bstore-kv-sync" \
                    and self.only in (None, me):
                self.entered.set()
                self.gate.wait()
            if os.path.basename(path) == "block" and name in self.fail:
                raise self.fail.pop(name)
            out = real(fd, *args)
            if is_sync and os.path.isfile(path):
                self.sizes[path] = os.path.getsize(path)
            self.log.append((name, "end", threading.get_ident(), path))
            return out
        return call

    def hold(self) -> None:
        self.entered.clear()
        self.gate.clear()

    def release(self) -> None:
        self.gate.set()

    def of_block(self, name: str, edge: str = "end") -> list[int]:
        """Where in the log the block file's `name`s are."""
        return [n for n, (what, e, _t, p) in enumerate(self.log)
                if (what, e, os.path.basename(p)) == (name, edge, "block")]


@pytest.fixture
def syncs(monkeypatch):
    s = Syncs(monkeypatch)
    yield s
    s.release()


def _store(tmp_path, name="bs") -> BlueStore:
    s = BlueStore(str(tmp_path / name))
    s.mount()
    return s


async def _settled(store: BlueStore, landed: bool = False) -> None:
    """Wait, without blocking the loop, until the store's thread is idle
    and its callbacks have run; with `landed`, until every deferred
    write is on the block file as well and its record gone, as `flush()`
    waits."""
    q = store._q
    while q.queued or q.busy or store._done or (
            landed and (q.deferred_ops or q.deferred_done)):
        if landed:
            with q.cond:
                q.drain = bool(q.deferred_ops or q.deferred_done)
                q.cond.notify_all()
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
            if i == at and kill == "fail_at_block_sync":
                # between a group's block writes and their sync: the
                # next group that writes an extent has written it and
                # dies in the `fdatasync`
                await _settled(store)
                syncs.fail["fdatasync"] = OSError(errno.EIO, "injected")
            t = _transaction(txn)
            t.register_on_commit(lambda i=i: committed.add(i))
            try:
                store.queue_transaction(t)
            except StoreError as e:
                # a dead store takes no more: the kill has happened
                assert e.code == "EIO" and kill.startswith("fail_")
                assert i > at
                break
            # queued is readable, whatever has committed; a store that
            # died under the read has let its staged extents go, and
            # what it never wrote is EIO
            try:
                assert _model_of(store) == want[i], f"live state after {i}"
            except StoreError as e:
                assert e.code == "EIO" and store.failed is not None
                assert i >= at and kill.startswith("fail_")
                break
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
    if kill == "fail_at_block_sync" and "fdatasync" in syncs.fail:
        # no transaction from `at` on wrote an extent: nothing died
        assert committed == set(range(len(txns))) and found == want[-1]
    elif kill.startswith("fail_"):
        # the group that failed, and all behind it, never called back
        # (a transaction that changes nothing syncs nothing and may
        # pass the KV's hook)
        dead = min(set(range(len(txns))) - committed)
        assert dead >= at and committed == set(range(dead))
        # before the KV, or before the block file's sync: data landed,
        # metadata did not. After the log's sync: the group is replayed
        # whole at the mount
        assert found in want[dead:] if kill == "fail_after_wal" \
            else found == want[dead - 1]


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
    transaction wrote extents ahead of its commit: at the line and over
    it) and after a sync of the KV log, both begun after the
    transaction was queued."""
    async def main():
        store = _store(tmp_path)
        store.queue_transaction(Transaction().create_collection(CID))
        await _settled(store)
        marks = {}
        for i, size in enumerate([BIG, 10, BIG, UNDER, INLINE_MAX, BIG + AU]):
            def done(i=i):
                marks[i] = (marks[i], len(syncs.log))
            store.queue_transaction(_write(f"o{i}", size, done))
            marks[i] = len(syncs.log)
            if i % 2:
                await _settled(store)
        await _settled(store)
        for i, size in enumerate([BIG, 10, BIG, UNDER, INLINE_MAX, BIG + AU]):
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
            if size >= INLINE_MAX:      # the rule is strict
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


@pytest.mark.parametrize("hook", ["fail_before_kv", "fail_after_wal",
                                  "pwrite_enospc"])
def test_a_failed_group_fires_nothing_and_restores_the_allocator(
        tmp_path, syncs, hook):
    fired = []

    async def main():
        store = _store(tmp_path)
        store.queue_transaction(Transaction().create_collection(CID))
        store.queue_transaction(_write("keep"))
        await _settled(store)
        used = sum(store.alloc.bits)
        syncs.hold()
        if hook == "pwrite_enospc":
            # the device is full at the next block write: the three
            # queue while the thread stands in a deferred object's KV
            # sync (it writes no block before it), since no write waits
            # at the fixture's gate. Its one unit is its own: it commits
            syncs.fail["pwrite"] = OSError(errno.ENOSPC, "injected")
            store.queue_transaction(_write("held", 1))
            used += 1
            await asyncio.to_thread(syncs.entered.wait, 10)
        else:
            setattr(store if hook == "fail_before_kv" else store.kv,
                    hook, True)
        for i in range(3):
            store.queue_transaction(_write(
                f"lost{i}", on_commit=lambda: fired.append(1)))
        store.queue_transaction(Transaction().remove(CID, _gh("keep")))
        assert sum(store.alloc.bits) > used
        syncs.release()
        await _settled(store)
        assert fired == []
        assert sum(store.alloc.bits) == used    # theirs back, `keep`'s kept
        # (`held`'s acknowledged unit stays staged for the reads: a
        # dead store lands nothing more)
        assert len(store._pend_extents) == (hook == "pwrite_enospc")
        assert not syncs.fail
        assert isinstance(store.failed, OSError if hook == "pwrite_enospc"
                          else SimulatedCrash)
        with pytest.raises(StoreError) as ei:
            store.queue_transaction(_write("more"))
        assert ei.value.code == "EIO"
        late = []
        store.flush_commit(lambda: late.append(1))
        assert late == []                       # never: the store is dead
        store.umount()

    run(main())


# -- a staged extent is written by the commit thread -------------------------

@pytest.mark.parametrize("size", [UNDER, SHARD],
                         ids=["deferred", "shard"])
@pytest.mark.parametrize("with_loop", [True, False], ids=["loop", "plain"])
def test_block_writes_are_the_commit_threads_and_precede_its_syncs(
        tmp_path, syncs, size, with_loop):
    """No `pwrite` of the block file on the thread that queues, with a
    loop or without; in a group every `pwrite` ends before the
    `fdatasync` begins, and that ends before the KV log's `fsync`
    begins. A write under the line turns the order round: the log's
    sync that acknowledges it comes first, its `pwrite`s after, one
    `fdatasync` behind them all, and the records go with a log record
    after that."""
    def drive(store):
        store.queue_transaction(Transaction().create_collection(CID))
        for i in range(3):
            store.queue_transaction(_write(f"o{i}", size))
        return threading.get_ident()

    async def main():
        store = _store(tmp_path)
        caller = drive(store)
        await _settled(store)
        return store, caller

    if with_loop:
        store, caller = run(main())
    else:
        store = _store(tmp_path)
        caller = drive(store)
    deferred = size < INLINE_MAX
    assert store.stats()["txcs"] == 4
    assert store.stats()["block_writes"] == (0 if deferred else 3)
    store.umount()              # lands what was deferred
    assert store.stats()["block_writes"] == 3
    assert store.stats()["block_bytes_written"] == 3 * size
    assert store.stats()["deferred_ops"] == (3 if deferred else 0)
    assert store.stats()["deferred_bytes"] == (3 * size if deferred else 0)
    writes = [e for e in syncs.log if e[0] == "pwrite"]
    assert all(os.path.basename(p) == "block" for _n, _e, _t, p in writes)
    assert len(writes) == 6                     # begin and end
    assert caller not in {t for _n, _e, t, _p in writes}
    assert {t for _n, _e, t, _p in writes} \
        <= {t for n, _e, t, p in syncs.log
            if n == "fdatasync" and os.path.basename(p) == "block"}
    wrote = syncs.of_block("pwrite")
    began = syncs.of_block("pwrite", "begin")
    synced = syncs.of_block("fdatasync", "begin")
    log_syncs = [n for n, (what, e, _t, p) in enumerate(syncs.log)
                 if (what, e, os.path.basename(p)) == ("fsync", "end",
                                                       "wal.log")]
    if deferred:
        # every transaction's log sync, then the three writes, ONE
        # sync of the block file, and the records' removal after it
        assert len(synced) == 1
        assert len([n for n in log_syncs if n < began[0]]) >= 2
        assert wrote[-1] < synced[0] < log_syncs[-1]
        assert store.stats()["deferred_flushes"] == 1
        return
    # each group: its writes, then the block sync, then the log's; no
    # write of the next group slips between the two syncs
    assert not wrote or wrote[-1] < synced[-1]
    for before, s in zip([-1, *synced], synced):
        assert [n for n in wrote if before < n < s], \
            "a block sync that no write preceded"
        done, log_sync = (
            next(n for n, (what, e, _t, p) in enumerate(syncs.log)
                 if n > s and (what, e, os.path.basename(p)) == want)
            for want in (("fdatasync", "end", "block"),
                         ("fsync", "begin", "wal.log")))
        assert done < log_sync
        assert not [n for n in began if s < n < log_sync]


@pytest.mark.parametrize("size", [UNDER, SHARD],
                         ids=["deferred", "shard"])
def test_what_queues_behind_an_unwritten_object_sees_its_bytes(
        tmp_path, syncs, size):
    """A read, a partial overwrite and a clone queued behind a write
    whose extents are still only staged return its bytes; the block
    file has none of them yet. A write under the line stays staged past
    its commit, until its batch has landed."""
    async def main():
        store = _store(tmp_path)
        store.queue_transaction(Transaction().create_collection(CID))
        store.queue_transaction(_write("warm"))
        await _settled(store)
        syncs.hold()
        store.queue_transaction(_write("held", 1))
        await asyncio.to_thread(syncs.entered.wait, 10)
        # the thread stands in a sync: what follows stays staged
        data, patch = os.urandom(size), os.urandom(5000)
        store.queue_transaction(Transaction().write(CID, _gh("a"), 0, data))
        n_writes = len(syncs.of_block("pwrite", "begin"))
        assert store.read(CID, _gh("a")) == data
        assert store.read(CID, _gh("a"), 4090, 12) == data[4090:4102]
        store.queue_transaction(
            Transaction().write(CID, _gh("a"), 4000, patch)
            .clone(CID, _gh("a"), _gh("b")))
        patched = data[:4000] + patch + data[9000:]
        assert store.read(CID, _gh("a")) == patched
        assert store.read(CID, _gh("b")) == patched
        assert store.corrupt(CID, _gh("b"), 7)
        rotten = bytearray(patched)
        rotten[7] ^= 1
        assert store.read(CID, _gh("b")) == bytes(rotten)
        assert len(syncs.of_block("pwrite", "begin")) == n_writes
        assert store._pend_extents
        syncs.release()
        await _settled(store)
        # committed: `held`'s unit is deferred, and so is all of `a`
        # and `b` under the line (the patch is a whole new object)
        assert bool(store._deferred_by_unit) and \
            (len(store._pend_extents) > 1) == (size < INLINE_MAX)
        assert store.read(CID, _gh("a")) == patched     # staged or landed
        await _settled(store, landed=True)
        assert not store._pend_extents and not store._deferred_by_unit
        assert store.read(CID, _gh("a")) == patched     # the block file's
        assert store.read(CID, _gh("b")) == bytes(rotten)
        store.umount()
        return patched, bytes(rotten)

    patched, rotten = run(main())
    fresh = _fresh_model(str(tmp_path / "bs"))[CID.pg_seed]
    assert fresh["a"]["data"] == patched and fresh["b"]["data"] == rotten


@pytest.mark.parametrize("shape", ["whole_bytes", "whole_view",
                                   "whole_over_shorter", "unaligned",
                                   "partial_offset", "partial_shorter",
                                   "whole_deferred"])
def test_a_whole_object_write_is_staged_as_the_transactions_buffer(
        tmp_path, syncs, shape):
    """`Op.WRITE` at offset 0 over nothing longer (every push and
    write_full) stages the buffer `Transaction.write` was given, uncopied,
    where its length is whole units; any other write stages a private
    buffer. `bstore_txc` says which, and `stats()`. Under the line the
    same holds, and the bytes are `deferred_bytes` too."""
    data = os.urandom(SHARD + 100 if shape == "unaligned" else
                      UNDER if shape == "whole_deferred" else SHARD)
    given = memoryview(data).toreadonly() if shape == "whole_view" else data
    by_ref = shape.startswith("whole")

    async def main():
        store = _store(tmp_path)
        store.queue_transaction(Transaction().create_collection(CID))
        first = {"whole_over_shorter": SHARD - AU, "partial_offset": SHARD,
                 "partial_shorter": SHARD + AU}.get(shape)
        if first:
            store.queue_transaction(_write("a", first))
        await _settled(store)
        base = store.stats()
        tracer.enable()
        try:
            cursor = tracer.collector().last_seq()
            syncs.hold()
            t = Transaction().touch(CID, _gh("a")).write(
                CID, _gh("a"), AU if shape == "partial_offset" else 0, given)
            store.queue_transaction(t)
            staged = list(store._pend_extents.values())
            syncs.release()
            await _settled(store, landed=True)
            txc, = [s for s in tracer.collector().spans()
                    if s["seq"] > cursor and s["name"] == "bstore_txc"]
        finally:
            tracer.disable()
        after = store.stats()
        want = store.read(CID, _gh("a"))
        store.umount()
        return staged, txc["tags"], base, after, want

    staged, tags, base, after, got = run(main())
    assert got[AU if shape == "partial_offset" else 0:][:len(data)] == data
    assert tags["deferred_bytes"] == \
        (tags["bytes"] if shape == "whole_deferred" else 0)
    assert tags["deferred_wait_us"] == 0
    assert after["deferred_bytes"] - base["deferred_bytes"] \
        == tags["deferred_bytes"]
    assert staged and (all(v.obj is data for v in staged)) == by_ref
    assert all(v.readonly for v in staged)
    assert tags["bytes"] == sum(len(v) for v in staged) >= len(data)
    assert tags["by_ref_bytes"] == (tags["bytes"] if by_ref else 0)
    assert after["block_bytes_by_ref"] - base["block_bytes_by_ref"] \
        == tags["by_ref_bytes"]
    assert after["block_bytes_written"] - base["block_bytes_written"] \
        == tags["bytes"]


@pytest.mark.parametrize("legacy", [False, True], ids=["deflated", "raw"])
def test_the_freelist_is_one_deflated_value_and_a_raw_one_still_mounts(
        tmp_path, legacy):
    """Every group that allocates or frees logs the whole bitmap as one
    KV value, deflated (raw, a byte a unit, it was most of a group's
    log record and its JSON held the GIL against the loop); a store
    whose last group was written raw, by the program before this,
    mounts to the same allocator."""
    import zlib

    store = _store(tmp_path)
    store.queue_transaction(Transaction().create_collection(CID))
    for i in range(4):
        store.queue_transaction(_write(f"o{i}", SHARD))
    store.queue_transaction(Transaction().remove(CID, _gh("o1")))
    bits = bytes(store.alloc.bits)
    assert bits.count(1) == 3 * SHARD // AU and bits.count(0) == SHARD // AU
    value = store.kv.get(bluestore.P_SUPER, "freelist")
    assert value[:1] == b"\x78" and zlib.decompress(value) == bits
    assert len(value) < len(bits) // 10
    if legacy:
        raw = store.kv.transaction()
        raw.set(bluestore.P_SUPER, "freelist", bits)
        store.kv.submit_transaction(raw, sync=True)
    store.umount()
    again = _store(tmp_path)
    assert bytes(again.alloc.bits) == bits and again.alloc._free == SHARD // AU
    again.queue_transaction(_write("o9", SHARD))    # into the hole
    assert bytes(again.alloc.bits) == b"\x01" * len(bits)
    assert again.kv.get(bluestore.P_SUPER, "freelist")[:1] == b"\x78"
    again.umount()


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
    by_group = {g["tags"]["group"]: g["tags"] for g in groups}
    for s in txcs:
        assert set(s["tags"]) >= {"prepare_us", "queued_us", "block_sync_us",
                                  "kv_submit_us", "deliver_us", "ops",
                                  "bytes", "group", "ran_ahead",
                                  "block_write_us", "by_ref_bytes",
                                  "deferred_bytes", "deferred_wait_us"}
        assert s["tags"]["ran_ahead"] is False
        # the writes are a part of the block-sync leg, its group's, and
        # no fifth leg: the five still sum to the span
        assert 0 <= s["tags"]["block_write_us"] <= s["tags"]["block_sync_us"]
        assert s["tags"]["block_write_us"] \
            == by_group[s["tags"]["group"]]["block_write_us"]
        legs = sum(s["tags"][k] for k in ("prepare_us", "queued_us",
                                          "block_sync_us", "kv_submit_us",
                                          "deliver_us"))
        assert legs == pytest.approx(s["duration_us"], rel=0.05, abs=50)
    assert sorted(s["tags"]["bytes"] for s in txcs) == [AU, BIG + AU - BIG % AU
                                                        if BIG % AU else BIG]
    # `b`, ten bytes, is one deferred unit: acknowledged from its
    # group's KV sync and not landed inside these spans
    assert sorted(s["tags"]["deferred_bytes"] for s in txcs) == [0, AU]
    assert sum(g["tags"]["deferred_in"] for g in groups) == 1
    assert sum(g["tags"]["deferred_removed"] for g in groups) == 0
    # `a` replaced its object whole from the transaction's own buffer;
    # `b` was padded to its unit, so copied
    assert sorted(s["tags"]["by_ref_bytes"] for s in txcs) \
        == [0, max(s["tags"]["bytes"] for s in txcs)]
    for g in groups:
        assert set(g["tags"]) >= {"txcs", "block_synced", "kv_fsyncs",
                                  "block_bytes", "kv_bytes",
                                  "freelist_bytes", "group",
                                  "block_writes", "block_write_us",
                                  "block_sync_us", "kv_submit_us",
                                  "deferred_in", "deferred_removed"}
        assert g["tags"]["kv_fsyncs"] >= 1 and g["tags"]["kv_bytes"] > 0
        assert g["tags"]["block_write_us"] <= g["tags"]["block_sync_us"]
        assert g["tags"]["block_sync_us"] + g["tags"]["kv_submit_us"] \
            == pytest.approx(g["duration_us"], abs=1)
        assert bool(g["tags"]["block_writes"]) \
            == bool(g["tags"]["block_bytes"])
    # (`b`'s deferred unit is counted with the group that took it on)
    assert sum(g["tags"]["block_writes"] for g in groups) == 2
    assert sum(g["tags"]["txcs"] for g in groups) == 2
    assert {s["tags"]["group"] for s in txcs} \
        == {g["tags"]["group"] for g in groups}
    # the prepare is what `store_commit` measures: no sync inside it
    commits = [s for s in spans if s["name"] == "store_commit"]
    assert len(commits) == 2


# -- prepare computes nothing twice: keys once, csums as they came ------------

META = CollectionId.make_meta()
NOSNAP, NOGEN = 2 ** 64 - 2, 2 ** 64 - 1
#: the key FORMAT is the store's on-disk format: these are the bytes the
#: program before the memo wrote, and a store it wrote must still mount
GOLDEN = [
    ("pg_collection", CollectionId.make_pg(7, 0x1f, 3), None,
     "[7, 31, 3, false]"),
    ("meta_collection", META, None, "[-1, 0, -1, true]"),
    ("head", CID, Ghobject(pool=7, name="a"),
     '[7, 0, -1, false]\x01[7, "", "a", %d, %d, -1]' % (NOSNAP, NOGEN)),
    ("clone", CID, Ghobject(pool=7, nspace="ns", name="a", snap=4),
     '[7, 0, -1, false]\x01[7, "ns", "a", 4, %d, -1]' % NOGEN),
    ("generation", CID, Ghobject(pool=7, name="a").with_gen(12),
     '[7, 0, -1, false]\x01[7, "", "a", %d, 12, -1]' % NOSNAP),
    ("shard_object", CollectionId.make_pg(7, 5, 10),
     Ghobject(pool=7, name='b"é', shard=10),
     '[7, 5, 10, false]\x01[7, "", "b\\"\\u00e9", %d, %d, 10]'
     % (NOSNAP, NOGEN)),
]


@pytest.mark.parametrize("case,cid,oid,want", GOLDEN,
                         ids=[g[0] for g in GOLDEN])
def test_golden_key_bytes(tmp_path, case, cid, oid, want):
    store = _store(tmp_path)
    for _cold_then_warm in range(2):
        if oid is None:
            assert bluestore._cid_key(cid) == store._cid_key(cid) == want
            assert bluestore._cid_from(want) == cid
        else:
            assert bluestore._onode_key(cid, oid) \
                == store._onode_key(cid, oid) == want
            assert bluestore._oid_from(want.split("\x01")[1]) == oid
    # an equal id that is another object finds the same entry
    assert store._cid_key(CollectionId(**vars(cid))) == want.split("\x01")[0]
    assert store._key_encodes == (1 if oid is None else 2)
    if oid is not None:
        store.queue_transaction(Transaction().create_collection(cid)
                                .touch(cid, oid))
        assert store.kv.get(bluestore.P_ONODE, want) is not None
        assert store.kv.get(bluestore.P_COLL, want.split("\x01")[0]) == b"1"
    store.umount()


def _ids(n: int, salt: str = "") -> list[tuple[CollectionId, Ghobject]]:
    return [(_cid(i % 3), Ghobject(pool=7, name=f"{salt}{i:04d}",
                                   shard=i % 5 - 1)) for i in range(n)]


@pytest.mark.parametrize("order", ["cold_write_warm_read",
                                   "warm_write_cold_read"])
def test_a_store_reads_the_same_with_the_memo_cold_or_warm(tmp_path, order):
    """The memo changes when a key is encoded, never what it is: what
    one store wrote with none of its ids remembered another lists and
    reads with all of them remembered, and the other way round."""
    ids = _ids(40)
    blobs = {i: os.urandom(BIG if n % 4 == 0 else 300)
             for n, i in enumerate(ids)}

    def write(store):
        for c in range(3):
            store.queue_transaction(Transaction().create_collection(_cid(c)))
        for (cid, oid), data in blobs.items():
            store.queue_transaction(
                Transaction().touch(cid, oid).write(cid, oid, 0, data)
                .setattrs(cid, oid, {"n": oid.name.encode()}))

    def check(store):
        for c in range(3):
            listed = store.collection_list(_cid(c))
            assert listed == sorted(o for cc, o in ids if cc == _cid(c))
        for (cid, oid), data in blobs.items():
            assert store.read(cid, oid) == data
            assert store.getattr(cid, oid, "n") == oid.name.encode()

    first = _store(tmp_path)
    if order == "warm_write_cold_read":
        for cid, oid in ids:            # every id met before it is written
            assert not first.exists(cid, oid)
        assert len(first._keys) == len(ids) + 3
    else:
        assert not first._keys
    write(first)
    check(first)
    first.umount()
    second = _store(tmp_path)
    assert not second._keys
    if order == "cold_write_warm_read":
        check(second)                   # warms it
        assert len(second._keys) == len(ids) + 3
    check(second)
    second.umount()


class _KeyEncodings:
    """`json.dumps` counted where it encodes a KEY on the caller's
    thread: `cid_key`'s list, four with a bool last, or `oid_key`'s, six
    with two strings after the pool, and nothing else (an onode is a
    dict, the KV's log records are the commit thread's)."""

    def __init__(self, monkeypatch):
        self.n = 0
        real, me = bluestore.json.dumps, threading.get_ident()

        def dumps(obj, *args, **kwargs):
            if isinstance(obj, list) and threading.get_ident() == me and (
                    (len(obj) == 4 and isinstance(obj[3], bool)) or
                    (len(obj) == 6 and isinstance(obj[1], str)
                     and isinstance(obj[2], str))):
                self.n += 1
            return real(obj, *args, **kwargs)
        monkeypatch.setattr(bluestore.json, "dumps", dumps)

    def taken(self) -> int:
        n, self.n = self.n, 0
        return n


def _shard_pair(store, cid, name: str, data, csums=None):
    """A shard's data transaction and its PG-log transaction with the
    probes in front of them, as `ECBackend._sub_write_txn` ->
    `_stash_prev`, `local_apply("push")` and `PG.persist_meta` build
    them (a primary's pair; a replica's are one since PR 50): every
    id made anew by each of the three, as `coll()` and `ghobject()`
    make them. -> the two transactions."""
    def gh(n):
        return Ghobject(pool=cid.pool, name=n, shard=cid.shard)

    def coll():
        return CollectionId(**vars(cid))
    if store.exists(coll(), gh(name)):          # `_stash_prev`
        prev = Transaction()
        if store.exists(coll(), gh(name + ".prev")):
            prev.remove(coll(), gh(name + ".prev"))
        store.queue_transaction(
            prev.clone(coll(), gh(name), gh(name + ".prev")))
    c, g = coll(), gh(name)                     # `local_apply`, "push"
    data_txn = Transaction()
    if store.exists(c, g):
        data_txn.remove(c, g)
    data_txn.touch(c, g).write(c, g, 0, data, csums).setattrs(
        c, g, {"shard": b"3", "ec_size": b"4194304", "csum": b"[1, 2]",
               "version": b"[3, 9]"})
    c, m = coll(), gh("_pgmeta_")               # `persist_meta`
    meta_txn = Transaction()
    if not store.exists(c, m):
        meta_txn.touch(c, m)
    meta_txn.setattr(c, m, "pgmeta", b"{}").omap_setkeys(
        c, m, {f"log.{name}": b"{}"})
    return data_txn, meta_txn


def test_a_shards_transactions_encode_a_key_once(tmp_path, monkeypatch):
    """Warm, a shard's data transaction with its probes makes at most 3
    key encodings (the new object's one; the program before made 33 for
    the pair) and its meta transaction at most 1, and the `bstore_txc`
    span says how many its store made up to it."""
    cid = CollectionId.make_pg(7, 5, 3)

    async def main():
        store = _store(tmp_path)
        store.queue_transaction(Transaction().create_collection(cid))
        for t in _shard_pair(store, cid, "warm", os.urandom(SHARD)):
            store.queue_transaction(t)
        await _settled(store)
        count = _KeyEncodings(monkeypatch)
        tracer.enable()
        try:
            cursor = tracer.collector().last_seq()
            made = []
            for name in ("o1", "o2", "o1"):     # the last: an overwrite
                count.taken()
                data_txn, meta_txn = _shard_pair(store, cid, name,
                                                 os.urandom(SHARD))
                store.queue_transaction(data_txn)
                made.append(count.taken())
                store.queue_transaction(meta_txn)
                made.append(count.taken())
            await _settled(store)
            tags = [s["tags"]["key_encodes"]
                    for s in tracer.collector().spans()
                    if s["seq"] > cursor and s["name"] == "bstore_txc"]
        finally:
            tracer.disable()
        store.umount()
        return made, tags

    made, tags = run(main())
    # data, meta a pair; the overwrite's probes meet the rollback
    # generation's id for the first time, in a clone of its own
    assert made == [1, 0, 1, 0, 1, 0]
    assert all(d <= 3 and m <= 1 for d, m in zip(made[::2], made[1::2]))
    assert tags == [1, 0, 1, 0, 1, 0, 0]
    assert sum(tags) == sum(made)


def test_the_memo_stays_at_its_bound(tmp_path):
    store = _store(tmp_path)
    store.queue_transaction(Transaction().create_collection(_cid(0)))
    ids = _ids(bluestore.KEY_MEMO + 700, "m")
    for n, (cid, oid) in enumerate(ids):
        assert not store.exists(cid, oid)
        assert len(store._keys) <= bluestore.KEY_MEMO
        if n == 5:
            assert len(store._keys) == 9    # three collections, six ids
    # the ids', two collections' (the first's went to the context that
    # made it), and all three's again after the memo was emptied once
    assert store._key_encodes == len(ids) + 2 + 3
    cid, oid = ids[3]
    assert store._onode_key(cid, oid) == bluestore._onode_key(cid, oid)
    gh = Ghobject(pool=7, name="kept")
    store.queue_transaction(Transaction().write(_cid(0), gh, 0, b"x"))
    assert store.read(_cid(0), gh) == b"x"
    store.umount()


def test_a_context_checks_its_collection_once_and_forgets_with_rmcoll(
        tmp_path, monkeypatch):
    """Later ops of a transaction go on what its first op found of the
    collection; removing it in the same transaction takes that back."""
    store = _store(tmp_path)
    store.queue_transaction(Transaction().create_collection(CID))
    gets = []
    real = store.kv.get
    monkeypatch.setattr(store.kv, "get", lambda prefix, key: (
        gets.append(prefix), real(prefix, key))[1])
    gh = _gh("a")
    store.queue_transaction(
        Transaction().touch(CID, gh).write(CID, gh, 0, b"abc")
        .setattrs(CID, gh, {"k": b"v"}).omap_setkeys(CID, gh, {"o": b"1"})
        .truncate(CID, gh, 2))
    assert gets.count(bluestore.P_COLL) == 1
    assert store.read(CID, gh) == b"ab"
    with pytest.raises(StoreError) as e:
        store.queue_transaction(
            Transaction().remove(CID, gh).remove_collection(CID)
            .touch(CID, gh))
    assert e.value.code == "ENOENT"
    assert store.collection_exists(CID) and store.read(CID, gh) == b"ab"
    # made and used in one transaction: found in the context itself
    store.queue_transaction(Transaction().create_collection(_cid(9))
                            .touch(_cid(9), gh))
    assert store.exists(_cid(9), gh)
    store.umount()


def _crcs(data) -> list[int]:
    """What the encode's finisher makes of a shard beside the encode
    (`ECBackend._csums`): crc32c of each 4 KiB chunk."""
    import numpy as np
    from ceph_tpu.native import ec_native
    return [int(x) for x in ec_native.crc32c_blocks(
        np.frombuffer(data, dtype=np.uint8), AU)]


#: case -> (reused, how the write is made). `given` is what the writer
#: says of its blocks; the store takes it only where it is of the very
#: units it stages
HINT_CASES = {
    "honoured": True, "honoured_as_an_array": True,
    "honoured_over_a_shorter_object": True, "fragmented": True,
    "no_hint": False, "wrong_block_size": False, "wrong_count": False,
    "padded_length": False, "partial_overwrite": False,
    "partial_offset": False, "not_numbers": False, "negative": False,
    "deferred": True,
}


@pytest.mark.parametrize("case", HINT_CASES)
def test_a_write_may_carry_its_blocks_checksums(tmp_path, monkeypatch, case):
    """`Transaction.write(..., csums=(block, values))`: kept as the
    extents' csums, sliced by allocated run, where block is the
    allocation unit, there is one value a unit of the buffer as staged
    and the buffer is staged whole; computed in every other case. What
    the onode holds is `Checksummer.calculate`'s either way."""
    import numpy as np
    reused = HINT_CASES[case]
    size = {"padded_length": SHARD + 100, "deferred": UNDER}.get(
        case, SHARD)
    data = os.urandom(size)
    padded = data + bytes(-size % AU)
    good = _crcs(padded)
    given = {
        "honoured_as_an_array": (AU, np.asarray(good, dtype=np.uint32)),
        "no_hint": None,
        "wrong_block_size": (2 * AU, good[::2]),
        "wrong_count": (AU, good[:-1]),
        "not_numbers": (AU, [str(c) for c in good[:-1]] + ["x"]),
        "negative": (AU, [-1] + good[1:]),
    }.get(case, (AU, good))
    offset = AU if case == "partial_offset" else 0
    first = {"honoured_over_a_shorter_object": SHARD - AU,
             "partial_overwrite": SHARD + AU, "partial_offset": SHARD}.get(case)
    store = _store(tmp_path)
    store.queue_transaction(Transaction().create_collection(CID))
    if first:
        store.queue_transaction(_write("a", first))
    if case == "fragmented":
        # free runs of 5, 50 and 9 units, then the end of the device
        store.alloc = bluestore.BitmapAllocator.from_bytes(
            bytes([1] + [0] * 5 + [1] * 3 + [0] * 50 + [1] + [0] * 9 + [1]))
    computed = []
    real = store.csum.calculate
    monkeypatch.setattr(store.csum, "calculate", lambda chunk: (
        computed.append(len(chunk)), real(chunk))[1])
    base = store.stats()
    store.queue_transaction(
        Transaction().touch(CID, _gh("a")).write(CID, _gh("a"), offset, data,
                                                 given))
    on = store._onode(CID, _gh("a"))
    after = store.stats()
    monkeypatch.undo()
    want = store.read(CID, _gh("a"))
    assert want[offset:offset + size] == data
    whole = want + bytes(-len(want) % AU)
    at = 0
    for unit, count, crcs in on["extents"]:
        assert crcs == store.csum.calculate(
            whole[at * AU:(at + count) * AU]).tolist()
        assert all(type(c) is int for c in crcs)
        at += count
    assert at * AU == len(whole)
    if case == "fragmented":
        assert [c for _u, c, _ in on["extents"]] == [5, 50, 9, 64]
    assert bool(computed) != reused
    assert after["csum_bytes_reused"] - base["csum_bytes_reused"] \
        == (len(whole) if reused else 0)
    # (a write under the line lands with the `umount`)
    assert after["block_bytes_written"] - base["block_bytes_written"] \
        == (0 if case == "deferred" else len(whole))
    store.umount()
    assert store.stats()["block_bytes_written"] \
        - base["block_bytes_written"] == len(whole)
    fresh = BlueStore(str(tmp_path / "bs"))
    fresh.mount()
    assert fresh.read(CID, _gh("a")) == want    # through the csum check
    fresh.umount()


@pytest.mark.parametrize("where", ["first_block", "last_block",
                                   "second_run"])
def test_a_wrong_checksum_handed_in_reads_back_as_eio(tmp_path, syncs,
                                                      where):
    """The store keeps what it was told and verifies every read against
    it, as against its own: a wrong value is an EIO from the staged view
    and from a fresh mount, never bytes returned."""
    data = os.urandom(SHARD)
    crcs = _crcs(data)
    bad = {"first_block": 0, "last_block": len(crcs) - 1,
           "second_run": 7}[where]
    crcs[bad] ^= 0x10

    async def main():
        store = _store(tmp_path)
        store.queue_transaction(Transaction().create_collection(CID))
        store.queue_transaction(_write("warm"))
        await _settled(store)
        if where == "second_run":
            store.alloc = bluestore.BitmapAllocator.from_bytes(
                bytes([1] * 40 + [0] * 5 + [1]))
        syncs.hold()
        store.queue_transaction(_write("held", 1))
        await asyncio.to_thread(syncs.entered.wait, 10)
        store.queue_transaction(
            Transaction().write(CID, _gh("a"), 0, data, (AU, crcs))
            .write(CID, _gh("b"), 0, data, (AU, _crcs(data))))
        assert store._pend_extents
        with pytest.raises(StoreError) as e:
            store.read(CID, _gh("a"))
        assert store.read(CID, _gh("b")) == data
        syncs.release()
        await _settled(store, landed=True)      # `held`'s unit too
        assert not store._pend_extents
        with pytest.raises(StoreError) as e2:
            store.read(CID, _gh("a"))           # the block file's
        store.umount()
        return e.value, e2.value

    staged, written = run(main())
    assert staged.code == written.code == "EIO"
    assert "csum mismatch" in str(staged) and "csum mismatch" in str(written)
    # (of the hole's five units `held` took one: the first run has four)
    assert f"(+{(bad - 4 if where == 'second_run' else bad) * AU} bytes)" \
        in str(staged)
    fresh = BlueStore(str(tmp_path / "bs"))
    fresh.mount()
    with pytest.raises(StoreError) as e3:
        fresh.read(CID, _gh("a"))
    assert e3.value.code == "EIO"
    assert fresh.read(CID, _gh("b")) == data
    assert fresh.getattrs(CID, _gh("a")) == {}  # the onode itself is whole
    fresh.umount()


@pytest.mark.parametrize("backend", ["memstore", "filestore"])
def test_a_store_that_keeps_no_checksums_ignores_them(tmp_path, backend):
    """`csum_block` 0 tells a writer to parse nothing; a write that
    carries checksums all the same stores the same bytes."""
    from ceph_tpu.objectstore import FileStore, MemStore
    store = MemStore() if backend == "memstore" \
        else FileStore(str(tmp_path / "fs"))
    assert store.csum_block == 0 and BlueStore.csum_block == AU
    store.mkfs()
    store.mount()
    store.queue_transaction(Transaction().create_collection(CID))
    data = os.urandom(SHARD)
    wrong = [c ^ 1 for c in _crcs(data)]
    store.queue_transaction(
        Transaction().write(CID, _gh("a"), 0, data, (AU, wrong))
        .write(CID, _gh("b"), 0, data)
        .write(CID, _gh("c"), 100, data[:5000], (AU, wrong[:1])))
    assert store.read(CID, _gh("a")) == store.read(CID, _gh("b")) == data
    assert store.read(CID, _gh("c")) == bytes(100) + data[:5000]
    store.umount()
    if backend == "filestore":
        again = FileStore(str(tmp_path / "fs"))
        again.mount()
        assert again.read(CID, _gh("a")) == data
        again.umount()
