"""BlueStore's deferred write path against the plain model of it
(`benchmarks/reference_deferred.py`): a write under the line rides its
transaction's KV batch, is acknowledged from the KV's one sync, lands on
its allocation units behind that, and a kill at any of its five stages
leaves a store that a mount replays into what was acknowledged. With the
recording `fsync` / `pwrite` of tests/test_bluestore_commit.py."""
from __future__ import annotations

import asyncio
import os
import shutil
import threading
import time

import pytest

from benchmarks import reference_bluestore as ref
from benchmarks import reference_deferred as dref
from ceph_tpu.kv import LSMStore
from ceph_tpu.kv import lsm
from ceph_tpu.objectstore import bluestore
from ceph_tpu.objectstore.bluestore import AU, INLINE_MAX, BlueStore
from ceph_tpu.objectstore.store import StoreError, Transaction
from ceph_tpu.utils import tracer
from ceph_tpu.utils.crash import SimulatedCrash

from tests.test_bluestore_commit import (CID, UNDER, _fresh_model, _gh,
                                         _model_of, _settled, _store,
                                         _transaction, syncs)  # noqa: F401
from tests.test_cluster import run

SEEDS = range(8)


def _records(path: str) -> dict[str, bytes]:
    """The deferred records in the KV of a store's directory, read
    without mounting it (a mount replays them)."""
    kv = LSMStore(os.path.join(path, "db"))
    kv.open()
    try:
        return dict(kv.iterate(bluestore.P_DEFERRED))
    finally:
        kv.close()


def _units_hold(path: str, record: bytes) -> bool:
    """Whether the block file of `path` holds a record's bytes on its
    units."""
    with open(os.path.join(path, "block"), "rb") as f:
        for unit, data in bluestore._record_extents(record):
            f.seek(unit * AU)
            if f.read(len(data)) != data:
                return False
    return True


def _cut_unsynced(syncs, path: str, copy: str) -> None:
    """A kill of the machine: what no sync covered is gone."""
    for f, size in syncs.sizes.items():
        if f.startswith(path + "/") and os.path.exists(copy + f[len(path):]):
            os.truncate(copy + f[len(path):], size)
    for name in ("block", "db/wal.log"):
        if os.path.join(path, name) not in syncs.sizes:
            os.truncate(os.path.join(copy, name), 0)   # never synced


def _step(syncs) -> None:
    """Let the held thread through this sync, to stand in its next."""
    syncs.entered.clear()
    syncs.gate.set()
    syncs.gate.clear()


def _drain(store) -> None:
    with store._q.cond:
        store._q.drain = True
        store._q.cond.notify_all()


async def _entered(syncs) -> None:
    assert await asyncio.to_thread(syncs.entered.wait, 10)


def _stages_a_deferred_write(before: dict, after: dict) -> bool:
    """Whether the transaction between two models rewrites some object
    to a length under the line (the store rewrites an object whole)."""
    for c, coll in after.items():
        for o, obj in coll.items():
            old = before.get(c, {}).get(o)
            if (old is None or old["data"] != obj["data"]) and \
                    dref.is_deferred(len(obj["data"]), INLINE_MAX):
                return True
    return False


# -- seeded transactions, killed at each of the five stages -------------------

@pytest.mark.parametrize("stage", dref.STAGES)
@pytest.mark.parametrize("seed", SEEDS)
def test_a_kill_at_each_stage_mounts_to_what_was_acknowledged(
        tmp_path, syncs, seed, stage):
    txns = ref.make_transactions(seed, n=18)
    want = ref.live(txns)
    at = next(i for i in range(4 + seed % 5, len(txns))
              if _stages_a_deferred_write(want[i - 1], want[i]))
    txns, want = txns[:at + 1], want[:at + 1]
    path = str(tmp_path / "bs")
    copies = []      # (directory, was the unsynced part kept)

    def kill(keep_unsynced: bool) -> None:
        copy = str(tmp_path / f"killed{len(copies)}")
        shutil.copytree(path, copy)
        if not keep_unsynced:
            _cut_unsynced(syncs, path, copy)
        copies.append((copy, keep_unsynced))

    async def main():
        store = BlueStore(path)
        store.mount()
        for txn in txns[:at]:
            store.queue_transaction(_transaction(txn))
        await _settled(store, landed=True)
        assert not _records(path)
        fired = []
        last = _transaction(txns[at])
        last.register_on_commit(lambda: fired.append(1))
        if stage == "queued":
            syncs.hold()
            store.queue_transaction(last)
            await _entered(syncs)
            kill(False)
            assert fired == []
        else:
            store.queue_transaction(last)
            await _settled(store)
            assert fired == [1] and store._q.deferred_ops > 0
            if stage == "kv_synced":
                kill(False)
            else:
                syncs.hold()
                _drain(store)
                await _entered(syncs)       # in the batch's `fdatasync`
                if stage == "written":
                    kill(False)
                    kill(True)
                else:
                    _step(syncs)
                    await _entered(syncs)   # in the removal's log sync
                    if stage == "block_synced":
                        kill(False)
        syncs.release()
        await _settled(store, landed=True)
        if stage == "record_removed":
            kill(False)
        assert _model_of(store) == want[at]
        store.umount()

    run(main())
    expect = dref.at_kill(stage)
    for copy, kept in copies:
        records = _records(copy)
        assert bool(records) == expect["record"], (stage, kept)
        for value in records.values():
            on_units = _units_hold(copy, value)
            if expect["on_units"] is not None:
                assert on_units == expect["on_units"]
            elif kept:                      # else the kill decided: a
                assert on_units             # unit inside the synced
                                            # length may have kept them
        found = _fresh_model(copy)          # mounts: the replay
        for c in {ref.collection_of(t) for t in txns}:
            assert found.get(c) in dref.states_after_kill(
                txns, at, stage, c), (stage, c)
        if expect["acknowledged"]:
            assert found == want[at]
        # after the replay: on its units, and no record left
        assert dref.after_replay(stage)["record"] is False
        assert not _records(copy)


@pytest.mark.parametrize("size", [1, AU - 1, AU, AU + 1, UNDER, INLINE_MAX - 1,
                                  INLINE_MAX, INLINE_MAX + 1])
def test_the_reference_and_the_store_draw_the_same_line(tmp_path, size):
    """Under `prefer_deferred_size` a write has a record until it has
    landed, at the line and over it never; the reference's device model
    says the same, and reads the same bytes back after its replay."""
    store = _store(tmp_path)
    store.queue_transaction(Transaction().create_collection(CID))
    data = os.urandom(size)
    store.queue_transaction(Transaction().write(CID, _gh("a"), 0, data))
    deferred = dref.is_deferred(size, INLINE_MAX)
    assert bool(store._q.deferred_ops) == deferred
    assert len(_records(store.path)) == int(deferred)
    on = store._onode(CID, _gh("a"))
    assert sum(c for _u, c, _ in on["extents"]) == dref.units(size, AU)
    model = dref.Device(AU, INLINE_MAX)
    w = model.write_full("a", data, upto="kv_synced")
    assert w["deferred"] == deferred
    assert len(model.kv["records"]) == int(deferred)
    killed = model.kill(keep_unsynced=False)
    assert killed.replay() == int(deferred)
    assert killed.replay() == 0 and killed.read("a") == data
    store.umount()
    assert not _records(store.path)
    assert _fresh_model(store.path)[CID.pg_seed]["a"]["data"] == data


def test_an_empty_object_is_an_onode_with_no_extents(tmp_path):
    store = _store(tmp_path)
    store.queue_transaction(Transaction().create_collection(CID)
                            .touch(CID, _gh("t")).write(CID, _gh("w"), 0, b"")
                            .setattrs(CID, _gh("s"), {"k": b"v"}))
    for name in "tws":
        on = store._onode(CID, _gh(name))
        assert on["size"] == 0 and not on.get("extents")
        assert set(on) <= {"size", "attrs"}
        assert store.read(CID, _gh(name)) == b""
    assert not store._q.deferred_ops and sum(store.alloc.bits) == 0
    store.queue_transaction(Transaction().write(CID, _gh("t"), 0, b"x" * 9)
                            .truncate(CID, _gh("t"), 0))
    assert store.read(CID, _gh("t")) == b""
    store.umount()
    assert _fresh_model(store.path)[CID.pg_seed]["t"]["data"] == b""


# -- the units under a pending write -------------------------------------------

def _extents(store, name):
    return [(u, c) for u, c, _ in store._onode(CID, _gh(name))["extents"]]


@pytest.mark.parametrize("how", ["overwrite", "remove"])
def test_units_freed_under_a_pending_write_wait_for_it_to_land(
        tmp_path, syncs, how):
    """The classic way to lose data here: `a`'s deferred write is
    acknowledged and not landed, a later transaction frees its units,
    and a third is handed them and writes first. They are not handed
    out until the deferred write has landed."""
    async def main():
        store = _store(tmp_path)
        store.queue_transaction(Transaction().create_collection(CID))
        store.queue_transaction(
            Transaction().write(CID, _gh("a"), 0, os.urandom(UNDER)))
        await _settled(store)
        old = _extents(store, "a")
        assert store._q.deferred_ops == len(old)
        new = os.urandom(UNDER)
        store.queue_transaction(
            Transaction().write(CID, _gh("a"), 0, new) if how == "overwrite"
            else Transaction().remove(CID, _gh("a")))
        await _settled(store)
        # committed: the KV's freelist has them free, the allocator not
        for unit, count in old:
            assert store._durable_bits[unit:unit + count] == bytes(count)
            assert store.alloc.bits[unit:unit + count] == b"\x01" * count
        big = os.urandom(INLINE_MAX + UNDER)
        store.queue_transaction(Transaction().write(CID, _gh("b"), 0, big))
        await _settled(store)
        taken = {u for unit, count in old for u in range(unit, unit + count)}
        assert not taken & {u for unit, count in _extents(store, "b")
                            for u in range(unit, unit + count)}
        await _settled(store, landed=True)
        for unit, count in old:             # landed: free at last
            assert store.alloc.bits[unit:unit + count] == bytes(count)
        store.queue_transaction(
            Transaction().write(CID, _gh("c"), 0, os.urandom(UNDER)))
        assert taken & {u for unit, count in _extents(store, "c")
                        for u in range(unit, unit + count)}
        await _settled(store, landed=True)
        assert store.read(CID, _gh("b")) == big
        if how == "overwrite":
            assert store.read(CID, _gh("a")) == new
        else:
            assert not store.exists(CID, _gh("a"))
        model = _model_of(store)
        store.umount()
        return model

    model = run(main())
    assert _fresh_model(str(tmp_path / "bs")) == model


def test_a_clone_and_a_truncate_of_a_pending_object(tmp_path, syncs):
    async def main():
        store = _store(tmp_path)
        store.queue_transaction(Transaction().create_collection(CID))
        data = os.urandom(UNDER)
        store.queue_transaction(Transaction().write(CID, _gh("a"), 0, data))
        await _settled(store)
        assert store._q.deferred_ops
        store.queue_transaction(Transaction().clone(CID, _gh("a"), _gh("b"))
                                .truncate(CID, _gh("a"), 5000))
        assert store.read(CID, _gh("b")) == data
        assert store.read(CID, _gh("a")) == data[:5000]
        assert not set(_extents(store, "a")) & set(_extents(store, "b"))
        await _settled(store)
        assert len(syncs.of_block("pwrite")) == 0     # all three pending
        assert store.read(CID, _gh("b")) == data
        await _settled(store, landed=True)
        assert store.read(CID, _gh("b")) == data
        assert store.read(CID, _gh("a")) == data[:5000]
        assert store.stats()["deferred_ops"] == 3
        store.umount()
        return data

    data = run(main())
    fresh = _fresh_model(str(tmp_path / "bs"))[CID.pg_seed]
    assert fresh["b"]["data"] == data and fresh["a"]["data"] == data[:5000]


def test_a_read_between_acknowledgement_and_block_write(tmp_path, syncs):
    """Served from the staged view while the block file has nothing of
    it; once landed it is the block file's bytes that are read, and
    verified: a flipped bit there is EIO."""
    async def main():
        store = _store(tmp_path)
        store.queue_transaction(Transaction().create_collection(CID))
        data = os.urandom(UNDER)
        fired = []
        t = Transaction().write(CID, _gh("a"), 0, data)
        t.register_on_commit(lambda: fired.append(1))
        store.queue_transaction(t)
        await _settled(store)
        (unit, _count), = _extents(store, "a")
        assert fired == [1] and unit in store._pend_extents
        assert os.path.getsize(os.path.join(store.path, "block")) == 0
        assert store.read(CID, _gh("a")) == data
        assert store.read(CID, _gh("a"), 4090, 12) == data[4090:4102]
        await _settled(store, landed=True)
        assert not store._pend_extents
        assert store.read(CID, _gh("a")) == data
        with open(os.path.join(store.path, "block"), "r+b") as f:
            f.seek(unit * AU + 9)
            f.write(bytes([data[9] ^ 0x20]))
        with pytest.raises(StoreError) as e:
            store.read(CID, _gh("a"))
        assert e.value.code == "EIO" and "csum mismatch" in str(e.value)
        store.umount()

    run(main())


def test_a_replay_run_twice_gives_the_same_store(tmp_path, syncs,
                                                 monkeypatch):
    async def main():
        store = _store(tmp_path)
        store.queue_transaction(Transaction().create_collection(CID))
        for i in range(5):
            store.queue_transaction(Transaction().write(
                CID, _gh(f"o{i}"), 0, os.urandom(UNDER - i * 1000)))
        store.queue_transaction(Transaction().write(
            CID, _gh("o1"), 0, os.urandom(AU)))     # frees under a record
        await _settled(store)
        model = _model_of(store)
        for name in ("once", "twice"):
            copy = str(tmp_path / name)
            shutil.copytree(store.path, copy)
            _cut_unsynced(syncs, store.path, copy)
        store.umount()
        return model

    model = run(main())
    once, twice = str(tmp_path / "once"), str(tmp_path / "twice")
    assert len(_records(once)) == 6
    # the first replay of `twice` dies between its block sync and the
    # removal of the records
    real = LSMStore.submit_transaction
    monkeypatch.setattr(LSMStore, "submit_transaction", lambda *a, **kw: (
        _ for _ in ()).throw(SimulatedCrash("killed in the replay")))
    dying = BlueStore(twice)
    with pytest.raises(SimulatedCrash):
        dying.mount()
    os.close(dying._fd)
    monkeypatch.setattr(LSMStore, "submit_transaction", real)
    assert len(_records(twice)) == 6
    assert _fresh_model(once) == _fresh_model(twice) == model
    assert not _records(once) and not _records(twice)
    with open(os.path.join(once, "block"), "rb") as a, \
            open(os.path.join(twice, "block"), "rb") as b:
        assert a.read() == b.read()
    again = BlueStore(twice)
    again.mount()
    assert again.stats()["deferred_replayed"] == 0
    again.umount()


# -- the bound, the syncs, the batch -------------------------------------------

def test_the_bound_on_pending_bytes_stalls_prepare_and_lets_go(
        tmp_path, syncs):
    async def main():
        store = _store(tmp_path)
        store.deferred_max_bytes = 3 * AU
        store.queue_transaction(Transaction().create_collection(CID))
        await _settled(store)
        tracer.enable()
        try:
            cursor = tracer.collector().last_seq()
            syncs.hold()
            store.queue_transaction(
                Transaction().write(CID, _gh("a"), 0, os.urandom(2 * AU)))
            await _entered(syncs)           # nothing can land now
            assert store._q.deferred_bytes == 2 * AU
            threading.Timer(0.2, syncs.release).start()
            t0 = time.perf_counter()
            store.queue_transaction(        # two more units: over it
                Transaction().write(CID, _gh("b"), 0, os.urandom(2 * AU)))
            waited = time.perf_counter() - t0
            await _settled(store)
            txcs = [s["tags"] for s in tracer.collector().spans()
                    if s["seq"] > cursor and s["name"] == "bstore_txc"]
        finally:
            tracer.disable()
        assert waited >= 0.15
        assert [t["deferred_wait_us"] > 1e5 for t in txcs] == [False, True]
        # `a` had to land for `b` to be let in
        assert store.stats()["deferred_flushes"] >= 1
        assert store.stats()["deferred_pending_peak"] == 2 * AU
        assert store.read(CID, _gh("b")) and store.read(CID, _gh("a"))
        # a write that alone is over the bound is let in when it is alone
        await _settled(store, landed=True)
        store.queue_transaction(
            Transaction().write(CID, _gh("c"), 0, os.urandom(5 * AU)))
        await _settled(store, landed=True)
        assert store._q.deferred_bytes == 0
        store.umount()

    run(main())


def test_an_acknowledgement_under_the_line_follows_exactly_one_sync(
        tmp_path, syncs):
    """Of the KV's log, and none of the block file: between the queueing
    and the callback there is one whole sync."""
    async def main():
        store = _store(tmp_path)
        store.queue_transaction(Transaction().create_collection(CID))
        await _settled(store)
        between = {}
        for name, size in (("a", UNDER), ("b", 8192), ("c", 1)):
            at = len(syncs.log)
            t = Transaction().write(CID, _gh(name), 0, os.urandom(size))
            t.register_on_commit(
                lambda name=name, at=at: between.setdefault(
                    name, syncs.log[at:]))
            store.queue_transaction(t)
            await _settled(store)
        assert store.stats()["acks_before_sync"] == 0
        store.umount()
        return between

    for name, log in run(main()).items():
        whole = [(what, os.path.basename(p)) for what, edge, _t, p in log
                 if edge == "end" and what != "pwrite"]
        assert whole == [("fsync", "wal.log")], (name, log)
        assert not [e for e in log if e[0] == "pwrite"]
    need = dref.least_device_bytes(65536, 8, 3, 4096, AU, INLINE_MAX)
    assert need["syncs_before_ack"] == ("kv",) and need["deferred"]


def test_a_batch_lands_at_64_extents_with_one_sync(tmp_path, syncs):
    """`DEFERRED_BATCH_OPS` extents make a batch due: one `fdatasync`
    for all of them, behind their acknowledgements; the next KV batch
    removes their records; `flush()` drains what is left."""
    async def main():
        store = _store(tmp_path)
        store.queue_transaction(Transaction().create_collection(CID))
        fired = []
        tracer.enable()
        try:
            cursor = tracer.collector().last_seq()
            for i in range(bluestore.DEFERRED_BATCH_OPS + 6):
                t = Transaction().write(CID, _gh(f"o{i}"), 0,
                                        os.urandom(8192))
                t.register_on_commit(lambda i=i: fired.append(i))
                store.queue_transaction(t)
                if i % 8 == 7:
                    await _settled(store)
            await _settled(store)
            spans = [s for s in tracer.collector().spans()
                     if s["seq"] > cursor]
        finally:
            tracer.disable()
        stats = store.stats()
        left = store._q.deferred_ops
        store.flush()
        return stats, left, spans, store.stats(), len(fired), store

    stats, left, spans, after, fired, store = run(main())
    assert fired == bluestore.DEFERRED_BATCH_OPS + 6
    assert stats["deferred_flushes"] == 1 and stats["block_syncs"] == 1
    assert stats["deferred_ops"] == 70 - left >= bluestore.DEFERRED_BATCH_OPS
    assert after["deferred_flushes"] == 2 and after["deferred_ops"] == 70
    assert after["deferred_bytes"] == 70 * 8192 == after["block_bytes_written"]
    assert not _records(store.path) and store._q.deferred_bytes == 0
    flush, = [s["tags"] for s in spans if s["name"] == "bstore_deferred_flush"]
    assert flush["ops"] == stats["deferred_ops"]
    assert flush["bytes"] == flush["ops"] * 8192
    # (what was staged behind the batch while it landed, at most)
    assert flush["pending_bytes"] in range(0, left * 8192 + 1, 8192)
    assert 0 < flush["median_lag_us"] <= flush["oldest_lag_us"]
    assert flush["write_us"] > 0 and flush["sync_us"] > 0
    groups = [s["tags"] for s in spans if s["name"] == "bstore_kv_sync"]
    assert sum(g["deferred_in"] for g in groups) == 70
    # an extent is counted in the group that acknowledged it, the
    # batch's sync in the group that came next on its thread
    assert sum(g["block_bytes"] for g in groups) == 70 * 8192
    assert sum(g["block_writes"] for g in groups) == 70
    assert sum(g["block_synced"] for g in groups) == 1
    removed = [g["deferred_removed"] for g in groups]
    assert sum(removed) in (0, flush["records"])
    txcs = [s["tags"] for s in spans if s["name"] == "bstore_txc"]
    assert all(t["deferred_bytes"] == t["bytes"] == 8192 for t in txcs[1:])
    assert all(t["ran_ahead"] is False for t in txcs)
    store.umount()


def test_flush_takes_the_records_of_a_batch_that_landed_by_itself(tmp_path):
    """A batch that fell due by its size has landed and its records
    wait for the next KV batch: `flush()` does not return before they
    are gone, so that a second mount finds nothing to replay."""
    async def main():
        store = _store(tmp_path)
        store.queue_transaction(Transaction().create_collection(CID))
        for i in range(bluestore.DEFERRED_BATCH_OPS):
            store.queue_transaction(
                Transaction().write(CID, _gh(f"o{i}"), 0, os.urandom(AU)))
        await _settled(store)
        q = store._q
        assert store.stats()["deferred_flushes"] == 1 and not q.deferred_ops
        assert q.deferred_done and _records(store.path)
        store.flush()
        assert not q.deferred_done and not q.drain
        assert not _records(store.path)
        second = BlueStore(store.path)
        second.mount()
        assert second.stats()["deferred_replayed"] == 0
        second.umount()
        store.umount()

    run(main())


def test_an_idle_store_lands_what_is_pending_after_a_while(
        tmp_path, monkeypatch):
    monkeypatch.setattr(bluestore, "DEFERRED_MAX_AGE_S", 0.15)

    async def main():
        store = _store(tmp_path)
        store.queue_transaction(Transaction().create_collection(CID))
        store.queue_transaction(
            Transaction().write(CID, _gh("a"), 0, os.urandom(100)))
        await _settled(store)
        assert store._q.deferred_ops == 1
        deadline = time.monotonic() + 5
        while store._q.deferred_ops and time.monotonic() < deadline:
            await asyncio.sleep(0.02)
        assert store.stats()["deferred_flushes"] == 1
        store.umount()

    run(main())


def test_the_osds_option_reaches_the_store(tmp_path):
    """`bluestore_prefer_deferred_size`: pushed to a BlueStore when the
    daemon is made and whenever it is set; a MemStore takes no notice;
    0 defers nothing."""
    from ceph_tpu.osd.daemon import OSD
    from ceph_tpu.utils.config import ConfigError

    store = BlueStore(str(tmp_path / "bs"))
    osd = OSD(0, [("127.0.0.1", 1)], store=store)
    assert osd.config.get("bluestore_prefer_deferred_size") == INLINE_MAX \
        == store.prefer_deferred_size
    osd.config.set("bluestore_prefer_deferred_size", 4096)
    assert store.prefer_deferred_size == 4096
    store.mount()
    store.queue_transaction(Transaction().create_collection(CID)
                            .write(CID, _gh("a"), 0, os.urandom(4095))
                            .write(CID, _gh("b"), 0, os.urandom(4096)))
    assert store._q.deferred_ops == 1 and store.stats()["block_writes"] == 1
    osd.config.set("bluestore_prefer_deferred_size", 0)
    store.queue_transaction(Transaction().write(CID, _gh("c"), 0, b"x"))
    assert store._q.deferred_ops == 1 and store.stats()["block_writes"] == 2
    store.umount()
    with pytest.raises(ConfigError):
        osd.config.set("bluestore_prefer_deferred_size", -1)
    plain = OSD(1, [("127.0.0.1", 1)])
    plain.config.set("bluestore_prefer_deferred_size", 8192)
    assert not hasattr(plain.store, "prefer_deferred_size")


# -- the KV under it ------------------------------------------------------------

def _put(db, *ops):
    t = db.transaction()
    for op in ops:
        (t.set if len(op) == 3 else t.rmkey)(*op)
    db.submit_transaction(t)


def test_a_value_costs_its_length_on_the_log(tmp_path):
    db = LSMStore(str(tmp_path / "db"))
    db.open()
    value = bytes(range(256)) * 32          # every byte value, 8 KiB
    _put(db, ("L", "0000000000000001", value))
    assert len(value) < db.stats["bytes_written"] < len(value) + 64
    db.close()
    again = LSMStore(str(tmp_path / "db"))
    again.open()
    assert again.get("L", "0000000000000001") == value
    again.close()


def test_a_record_set_and_deleted_in_one_memtable_reaches_no_run(tmp_path):
    db = LSMStore(str(tmp_path / "db"), flush_bytes=40_000)
    db.WAL_FLUSHES = 1000       # the memtable's threshold alone
    db.open()
    tracer.enable()
    try:
        cursor = tracer.collector().last_seq()
        for i in range(40):
            _put(db, ("O", f"onode{i:03d}", b"m" * 600),
                 ("L", f"{i:016x}", os.urandom(8192)))
            if i >= 2:                       # landed two transactions on
                _put(db, ("L", f"{i - 2:016x}"))
            db.maintain()
        spans = [s for s in tracer.collector().spans()
                 if s["seq"] > cursor and s["name"] == "kv_flush"]
    finally:
        tracer.disable()
    assert db.stats["memtable_flushes"] >= 1 == len(spans)
    tags = spans[0]["tags"]
    # the run holds the onodes and the records that were pending then
    # (three at most), none of the twenty and more that came and went
    assert tags["dropped"] >= 20 and tags["entries"] <= 40 - 20 + 3 + 20
    assert tags["bytes_out"] < 40 * 700 + 3 * 8300
    assert tags["bytes_in"] >= db.FLUSH_BYTES
    run_keys = [k for r in db._runs for k in r]
    assert sum(k.startswith("L\x00") for k in run_keys) <= 3
    # a record a run holds is shadowed when it goes, and gone for good
    # after the compaction
    assert [k for k, _v in db.iterate("L")] == ["0000000000000026",
                                                "0000000000000027"]
    _put(db, ("L", "0000000000000026"), ("L", "0000000000000027"))
    assert list(db.iterate("L")) == []
    db.compact()
    assert not [k for r in db._runs for k in r if k.startswith("L\x00")]
    assert len(list(db.iterate("O"))) == 40
    db.close()
    again = LSMStore(str(tmp_path / "db"))
    again.open()
    assert list(again.iterate("L")) == [] and len(list(again.iterate("O"))) == 40
    again.close()


def test_the_log_has_a_threshold_of_its_own(tmp_path):
    """Records that come and go fill the log and not the memtable: the
    log is cut when it passes `WAL_FLUSHES` memtables, whatever the
    memtable holds, and a mount replays no more than that."""
    db = LSMStore(str(tmp_path / "db"), flush_bytes=10_000)
    db.open()
    for i in range(12):
        _put(db, ("L", f"{i:016x}", os.urandom(8192)))
        _put(db, ("L", f"{i:016x}"))
        db.maintain()           # between two records: nothing is live
        assert os.path.getsize(db._wal_path()) < 4 * 10_000 + 8300
    assert db.stats["memtable_flushes"] >= 2 and not db._run_files
    assert list(db.iterate("L")) == []
    db.close()
    again = LSMStore(str(tmp_path / "db"))
    again.open()
    assert list(again.iterate("L")) == []
    again.close()


def test_no_submit_flushes_and_the_owners_call_does(tmp_path):
    db = LSMStore(str(tmp_path / "db"), flush_bytes=1000)
    db.open()
    for i in range(8):
        _put(db, ("O", f"k{i}", bytes(500)))
    assert db.stats["memtable_flushes"] == 0
    db.maintain()
    assert db.stats["memtable_flushes"] == 1 and len(db._run_files) == 1
    assert db.get("O", "k7") == bytes(500)
    db.close()


def test_compaction_leaves_a_span_and_files_of_an_older_format_raise(
        tmp_path):
    db = LSMStore(str(tmp_path / "db"), flush_bytes=400)
    db.open()
    tracer.enable()
    try:
        cursor = tracer.collector().last_seq()
        for i in range(60):
            _put(db, ("O", f"k{i % 20:02d}", bytes(150)))
            db.maintain()
        spans = [s["tags"] for s in tracer.collector().spans()
                 if s["seq"] > cursor and s["name"] == "kv_compact"]
    finally:
        tracer.disable()
    assert db.stats["compactions"] == len(spans) >= 1
    assert all(t["bytes_in"] > t["bytes_out"] > 0 and t["dropped"] > 0
               and t["entries"] <= 20 for t in spans)
    db.close()
    # a log record and a run written as JSON, by the program before
    old = tmp_path / "old"
    os.makedirs(old / "sst")
    rec = b'[["set", "O", "k", "v"]]'
    import struct
    (old / "wal.log").write_bytes(
        struct.pack("<II", len(rec), lsm._crc32c(rec)) + rec)
    with pytest.raises(IOError):
        LSMStore(str(old)).open()
    body = b'{"O\\u0000k": "v"}'
    (old / "wal.log").write_bytes(b"")
    (old / "sst" / "000001.sst").write_bytes(
        struct.pack("<I", lsm._crc32c(body)) + body)
    (old / "MANIFEST").write_text('{"runs": ["000001.sst"], "next": 2}')
    with pytest.raises(IOError):
        LSMStore(str(old)).open()
