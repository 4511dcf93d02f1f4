"""KeyValueDB (LSM) + BlueStore-specific tests: flush/compaction, WAL
replay, crash windows, csum-verified reads, allocator reuse.

Models the reference's store_test.cc BlueStore cases and
src/test/objectstore/test_kv.cc (KVTest: PutReopen, Compaction).
"""
from __future__ import annotations

import os

import pytest

from ceph_tpu.kv import KVSimulatedCrash, LSMStore, MemDB
from ceph_tpu.objectstore import (BlueStore, CollectionId, Ghobject,
                                  StoreError, Transaction)
from ceph_tpu.objectstore.bluestore import AU, INLINE_MAX
from ceph_tpu.objectstore.bluestore import (SimulatedCrash as
                                            BSSimulatedCrash)

CID = CollectionId.make_pg(3, 0x1)


def _put(db, prefix, key, val):
    t = db.transaction()
    t.set(prefix, key, val)
    db.submit_transaction(t)


# -- KV engine --------------------------------------------------------------

@pytest.mark.parametrize("engine", ["memdb", "lsm"])
def test_kv_basic_and_iterate(engine, tmp_path):
    db = MemDB() if engine == "memdb" else LSMStore(str(tmp_path / "db"))
    db.open()
    _put(db, "A", "k2", b"v2")
    _put(db, "A", "k1", b"v1")
    _put(db, "B", "k1", b"other")
    assert db.get("A", "k1") == b"v1"
    assert db.get("A", "missing") is None
    assert list(db.iterate("A")) == [("k1", b"v1"), ("k2", b"v2")]
    assert list(db.iterate("A", start="k2")) == [("k2", b"v2")]
    t = db.transaction()
    t.rmkey("A", "k1")
    db.submit_transaction(t)
    assert db.get("A", "k1") is None
    t = db.transaction()
    t.rmkeys_by_prefix("B")
    db.submit_transaction(t)
    assert list(db.iterate("B")) == []
    db.close()


def test_lsm_reopen_replays_wal(tmp_path):
    db = LSMStore(str(tmp_path / "db"))
    db.open()
    for i in range(20):
        _put(db, "P", f"k{i:03d}", f"v{i}".encode())
    db.close()
    db2 = LSMStore(str(tmp_path / "db"))
    db2.open()
    assert db2.get("P", "k007") == b"v7"
    assert len(list(db2.iterate("P"))) == 20
    db2.close()


def test_lsm_crash_between_wal_and_apply(tmp_path):
    db = LSMStore(str(tmp_path / "db"))
    db.open()
    _put(db, "P", "base", b"committed")
    db.fail_after_wal = True
    t = db.transaction()
    t.set("P", "crashed", b"recovered")
    with pytest.raises(KVSimulatedCrash):
        db.submit_transaction(t)
    db.close()                   # memtable never saw the record
    db2 = LSMStore(str(tmp_path / "db"))
    db2.open()                   # ... but WAL replay does
    assert db2.get("P", "base") == b"committed"
    assert db2.get("P", "crashed") == b"recovered"
    db2.close()


def test_lsm_flush_compaction_and_tombstones(tmp_path):
    db = LSMStore(str(tmp_path / "db"), flush_bytes=512)
    db.open()
    for i in range(50):
        _put(db, "P", f"k{i:03d}", bytes(64))
        db.maintain()           # the owner's call: no submit flushes
    t = db.transaction()
    t.rmkey("P", "k010")
    db.submit_transaction(t)
    assert len(db._run_files) > 1            # flushed more than once
    db.compact()
    assert len(db._run_files) == 1           # fully merged
    assert db.get("P", "k010") is None       # tombstone won the merge
    assert db.get("P", "k011") == bytes(64)
    # reopen from the compacted state
    db.close()
    db2 = LSMStore(str(tmp_path / "db"))
    db2.open()
    assert db2.get("P", "k010") is None
    assert db2.get("P", "k049") == bytes(64)
    db2.close()


# -- BlueStore --------------------------------------------------------------

def _mkstore(tmp_path, name="bs"):
    s = BlueStore(str(tmp_path / name))
    s.mkfs()
    s.mount()
    return s


def test_bluestore_large_write_extents_and_remount(tmp_path):
    s = _mkstore(tmp_path)
    s.queue_transaction(Transaction().create_collection(CID))
    oid = Ghobject(pool=3, name="big")
    data = os.urandom(INLINE_MAX + 3 * AU + 123)
    t = Transaction()
    t.write(CID, oid, 0, data)
    s.queue_transaction(t)
    on = s._onode(CID, oid)
    # one representation of data, extents, over the deferred line and
    # under it: nothing of an object's bytes lives in its onode
    assert set(on) == {"size", "extents", "attrs"} and not s._q.deferred_ops
    small = Ghobject(pool=3, name="small")
    s.queue_transaction(Transaction().write(CID, small, 0, data[:AU + 7]))
    assert set(s._onode(CID, small)) == {"size", "extents", "attrs"}
    assert s._q.deferred_ops == 1 and s.read(CID, small) == data[:AU + 7]
    assert s.read(CID, oid) == data
    s.umount()
    s2 = BlueStore(str(tmp_path / "bs"))
    s2.mount()
    assert s2.read(CID, oid) == data
    assert s2.stat(CID, oid)["size"] == len(data)
    assert s2.read(CID, Ghobject(pool=3, name="small")) == data[:AU + 7]
    s2.umount()


def test_bluestore_csum_detects_bitrot(tmp_path):
    s = _mkstore(tmp_path)
    s.queue_transaction(Transaction().create_collection(CID))
    oid = Ghobject(pool=3, name="rot")
    data = os.urandom(INLINE_MAX + AU)
    s.queue_transaction(Transaction().write(CID, oid, 0, data))
    unit = s._onode(CID, oid)["extents"][0][0]
    s.umount()
    # flip one bit inside the first extent on the "device"
    blk = str(tmp_path / "bs" / "block")
    with open(blk, "r+b") as f:
        f.seek(unit * AU + 100)
        b = f.read(1)
        f.seek(unit * AU + 100)
        f.write(bytes([b[0] ^ 0x40]))
    s2 = BlueStore(str(tmp_path / "bs"))
    s2.mount()
    with pytest.raises(StoreError) as ei:
        s2.read(CID, oid)
    assert ei.value.code == "EIO"
    s2.umount()


def test_bluestore_crash_before_kv_keeps_old_state(tmp_path):
    s = _mkstore(tmp_path)
    s.queue_transaction(Transaction().create_collection(CID))
    oid = Ghobject(pool=3, name="tx")
    old = os.urandom(INLINE_MAX + AU)
    s.queue_transaction(Transaction().write(CID, oid, 0, old))
    s.fail_before_kv = True
    with pytest.raises(BSSimulatedCrash):
        s.queue_transaction(
            Transaction().write(CID, oid, 0, os.urandom(INLINE_MAX + AU)))
    s.umount()
    s2 = BlueStore(str(tmp_path / "bs"))
    s2.mount()
    # the txc ordering: data landed but metadata did not -> old object
    assert s2.read(CID, oid) == old
    s2.umount()


def test_bluestore_allocator_reuses_freed_space(tmp_path):
    s = _mkstore(tmp_path)
    s.queue_transaction(Transaction().create_collection(CID))
    big = os.urandom(INLINE_MAX + 8 * AU)
    for i in range(6):
        oid = Ghobject(pool=3, name=f"cycle{i}")
        s.queue_transaction(Transaction().write(CID, oid, 0, big))
        s.queue_transaction(Transaction().remove(CID, oid))
    # freed extents must be recycled: the device bitmap stays bounded
    # instead of growing by 8 AUs per cycle
    used = sum(s.alloc.bits)
    assert used * AU < 3 * len(big)
    s.umount()
