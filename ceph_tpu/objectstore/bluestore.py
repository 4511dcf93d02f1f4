"""BlueStore-lite: ObjectStore on a raw block file + KeyValueDB metadata.

Re-creation of the reference BlueStore's architecture
(src/os/bluestore/BlueStore.cc) at framework scope:

  * one flat block file is the "raw device"; a bitmap allocator hands
    out 4 KiB allocation units (src/os/bluestore/BitmapAllocator) and
    its state persists through the same KV batch as the metadata it
    serves (FreelistManager): the whole bitmap as one value, deflated;
  * per-object metadata is an onode in the KV store (onode -> extent
    map -> blobs, BlueStore.cc _do_write/_do_alloc_write :16792,:16184):
    logical extents name (physical offset, length, crc32c), and every
    read verifies the stored csum and raises EIO on mismatch
    (bluestore_blob_t::verify_csum, bluestore_types.cc:840, read-time
    check BlueStore.cc:12234);
  * small writes are DEFERRED (_do_alloc_write sends a new blob shorter
    than bluestore_prefer_deferred_size down this path; `INLINE_MAX` is
    that line's default here, upstream's hdd value): the write is
    allocated, checksummed and staged as any other, the same extents in
    its onode, and its bytes ride the group's KV batch as a record under
    `P_DEFERRED` (bluestore_deferred_transaction_t under
    PREFIX_DEFERRED). `on_commit` fires from that ONE sync of the KV
    log, with no sync of the block file for it. The commit thread writes
    what is acknowledged to its units later, `DEFERRED_BATCH_OPS`
    extents to one `fdatasync` (_deferred_queue,
    _deferred_submit_unlock), and a later KV batch removes the records
    (_deferred_aio_finish). Until a write has landed, reads are served
    from its staged view; units that a later transaction frees do not
    return to the allocator while a deferred write to them is pending;
    `deferred_max_bytes` bounds what may be pending and `prepare` waits
    on it (bluestore_throttle_deferred_bytes); nothing waits longer
    than `DEFERRED_MAX_AGE_S` (bluestore_max_defer_interval); `flush()`
    drains; a mount applies the records a kill left (_deferred_replay).
    An empty object is an onode of size 0 with no extents: there is one
    representation of data;
  * large writes go data-first: extents are written to the block file
    and synced BEFORE the KV batch commits, so a crash in between
    leaves the old onode pointing at the old extents (BlueStore's txc
    ordering); freed extents return to the allocator only after the
    batch is durable. The caller's thread only STAGES an extent (units
    allocated, csums made, a read-only view of the bytes kept on the
    context, as _do_alloc_write queues bdev->aio_write on the txc): the
    commit thread `pwrite`s it, ahead of its group's sync. A write that
    replaces its object whole is staged as the transaction's own buffer,
    uncopied; until the KV holds the group, reads are served from the
    staged views;
  * a transaction is one atomic slice of a KV batch (the RocksDB
    WriteBatch role): apply is all-or-nothing at the KV WAL;
  * the commit is a pipeline (the txc state machine, _txc_state_proc
    :13556, and _kv_sync_thread :14191): `queue_transaction` PREPARES
    on the caller's thread (ops applied to staged onodes, units
    allocated, extents staged, csums made or taken from the write that
    brought them, the KV batch built), queues the context and returns.
    One commit thread a mounted store
    takes every context queued, writes their staged extents (the
    deferred ones apart), syncs the block file once if it wrote any,
    submits ONE synced KV batch for all of them and hands each context's
    `on_commit` back to the loop that queued it, in queue order; then,
    with the acknowledgements gone, it lands a batch of deferred writes
    if one is due and lets the KV flush or compact if that is due. The
    group is whatever queued while the last sync ran: no timer, no knob.
    A group's `bstore_kv_sync` span counts every extent its contexts
    staged, the deferred ones with them (`block_bytes`, `block_writes`:
    the thread that acknowledged them is the one that writes them), and
    what the thread synced and the KV wrote between two groups (a
    deferred batch's `fdatasync`, the KV's maintenance) is counted in
    the next group's span (`block_synced`, `kv_bytes`, `kv_fsyncs`):
    volumes are whole from span to span, times are the group's own.
    Reads see a queued transaction at once
    (`on_applied` is immediate, as upstream's is on BlueStore). A
    caller with no running loop (the tools, a plain test) waits for its
    context and gets the callbacks, or the commit's exception, before
    the call returns.

Idiomatic divergences: writes rewrite the object's extent set rather
than splicing sub-extents (the RMW/compression/blob-reuse machinery is
out of scope); collections/omap/attrs are KV prefixes C/M plus fields
in the onode record.
"""
from __future__ import annotations

import asyncio
import collections
import json
import os
import statistics
import struct
import threading
import time
import weakref
import zlib

import numpy as np

from ceph_tpu.kv.keyvaluedb import KeyValueDB, KVTransaction
from ceph_tpu.kv.lsm import LSMStore
from ceph_tpu.objectstore.store import (ObjectStore, Op, StoreError,
                                        Transaction)
from ceph_tpu.objectstore.types import (CollectionId, Ghobject, cid_from,
                                        cid_key, oid_from, oid_key)
from ceph_tpu.utils import tracer
from ceph_tpu.utils.crash import SimulatedCrash  # noqa: F401 (re-export)
from ceph_tpu.utils.dout import dout

AU = 4096                    # allocation unit (min_alloc_size)
#: the line under which a write is deferred: the default of
#: `BlueStore.prefer_deferred_size` (bluestore_prefer_deferred_size_hdd).
#: As upstream's, the rule is strict: a write of exactly this goes to
#: the block file first
INLINE_MAX = 64 * 1024
#: deferred extents that make a batch due (bluestore_deferred_batch_ops_hdd)
DEFERRED_BATCH_OPS = 64
#: bytes a store may hold staged for a deferred write and not landed
#: (bluestore_throttle_deferred_bytes); a batch is due past half of it
DEFERRED_MAX_BYTES = 128 * 1024 * 1024
#: seconds an acknowledged deferred write may wait for its batch on an
#: idle store (bluestore_max_defer_interval)
DEFERRED_MAX_AGE_S = 3.0
#: identities (a collection's, an object's in its collection) whose key
#: a store remembers. One store of the benchmark's deployment touches
#: about a hundred in a window: 16 ops in flight x a new object and its
#: rollback generation, its 32 PG-meta objects and 33 collections. Ten
#: times that, so that the long-lived ones are encoded again (the memo
#: is emptied when full) once in some 450 new objects
KEY_MEMO = 1024

# KV prefixes (the reference's column families, BlueStore.cc PREFIX_*)
P_SUPER = "S"
P_COLL = "C"
P_ONODE = "O"
P_OMAP = "M"
P_DEFERRED = "L"

_CLEAR = "\x00CLEAR\x00"     # in an omap overlay: the keys under it are gone
#: a context's states (the txc state machine) are `prepare`, on the
#: caller's thread; `queued`, `block_synced`, `kv_submitted`, on the
#: commit thread; `done`, where the callbacks ran; or `failed`. A caller
#: with no loop waits for one of these:
_SETTLED = ("kv_submitted", "done", "failed")


def _crc32c(data: bytes) -> int:
    from ceph_tpu.native import ec_native
    return ec_native.crc32c(data)


def _pwrite_all(fd: int, data, offset: int) -> None:
    """`os.pwrite` until all of `data` is written: positional and
    unbuffered, and a write may come back short."""
    view = memoryview(data)
    while view:
        n = os.pwrite(fd, view, offset)
        view, offset = view[n:], offset + n


# The key FORMAT: pure functions of frozen ids. A mounted store asks its
# memo (`BlueStore._cid_key`, `_onode_key`), which asks these once an id.

def _cid_key(cid: CollectionId) -> str:
    return json.dumps(cid_key(cid))


def _cid_from(key: str) -> CollectionId:
    return cid_from(json.loads(key))


def _oid_key(oid: Ghobject) -> str:
    return json.dumps(oid_key(oid))


def _oid_from(key: str) -> Ghobject:
    return oid_from(json.loads(key))


def _onode_key(cid: CollectionId, oid: Ghobject) -> str:
    return _cid_key(cid) + "\x01" + _oid_key(oid)


class BitmapAllocator:
    """AU-granular bitmap over the block file (BitmapAllocator +
    FreelistManager: the bitmap itself rides the commit batch). It
    counts its free units: a device that is full, as one that only ever
    takes new objects is after every allocation, grows at once and is
    not walked first."""

    def __init__(self, n_units: int = 0):
        self.bits = bytearray(n_units)        # 0 free, 1 used
        self._cursor = 0
        self._free = n_units

    def to_bytes(self) -> bytes:
        return bytes(self.bits)

    @classmethod
    def from_bytes(cls, blob: bytes) -> "BitmapAllocator":
        a = cls()
        a.bits = bytearray(blob)
        a._free = a.bits.count(0)
        return a

    def grow(self, n_units: int) -> None:
        if n_units > len(self.bits):
            self._free += n_units - len(self.bits)
            self.bits.extend(bytes(n_units - len(self.bits)))

    def _take(self, unit: int, count: int) -> None:
        self.bits[unit:unit + count] = b"\x01" * count
        self._free -= count

    def allocate(self, n_units: int) -> list[tuple[int, int]]:
        """Allocate `n_units`, possibly fragmented: [(unit, count)...],
        from the cursor on and once round. Grows the device when free
        space runs out."""
        out: list[tuple[int, int]] = []
        need = n_units
        bits = self.bits
        i, wrapped = self._cursor, False
        while need and self._free:
            i = bits.find(0, i)             # the next free unit
            if i < 0:
                if wrapped:
                    break
                i, wrapped = 0, True
                continue
            j = bits.find(1, i, i + need)   # the run's end, or enough
            if j < 0:
                j = min(len(bits), i + need)
            self._take(i, j - i)
            out.append((i, j - i))
            need -= j - i
            i = j
        self._cursor = i
        if need:
            base = len(bits)
            self.grow(base + need)
            self._take(base, need)
            out.append((base, need))
            self._cursor = base + need
        return out

    def free(self, extents: list[tuple[int, int]]) -> None:
        for unit, count in extents:
            self._free += self.bits.count(1, unit, unit + count)
            self.bits[unit:unit + count] = bytes(count)


class _CommitQueue:
    """What a store and its commit thread share. It refers to no store:
    the thread holds its store weakly, so a store dropped without
    `umount` (a killed daemon, a test's) is collected and its finalizer
    stops the thread."""

    def __init__(self):
        self.cond = threading.Condition()
        self.queued: list[_TxnCtx] = []     # prepared, in queue order
        self.busy = False                   # the thread is at work
        self.stop = False
        self.failed: BaseException | None = None    # a group's; for good
        # deferred writes; the commit thread alone changes the first
        # three, under `cond`: the acknowledged ones in the order they
        # are to land, and how many extents they are; the keys of the
        # records whose extents the block file holds, synced, for the
        # next KV batch to remove; the bytes staged for a deferred write
        # and not landed (the bound's); and whether someone waits for
        # all of it to be gone (`flush`, a `prepare` at the bound)
        self.deferred_ready: list[_Deferred] = []
        self.deferred_ops = 0
        self.deferred_done: list[str] = []
        self.deferred_bytes = 0
        self.drain = False

    def wait_for_work(self) -> None:
        """Under `cond`: until something is queued, the thread is told
        to stop, or deferred writes are to land (asked for, or waiting
        `DEFERRED_MAX_AGE_S`) or their records to go (asked for)."""
        while not self.queued and not self.stop:
            if self.failed is None:
                if self.drain and (self.deferred_ready
                                   or self.deferred_done):
                    return
                if self.deferred_ready:
                    left = self.deferred_ready[0].t_acked \
                        + DEFERRED_MAX_AGE_S - time.perf_counter()
                    if left <= 0:
                        return
                    self.cond.wait(left)
                    continue
            self.cond.wait()


def _stop_queue(q: _CommitQueue) -> None:
    with q.cond:
        q.stop = True
        q.cond.notify_all()


def _kv_sync_thread(ref, q: _CommitQueue) -> None:
    """The commit thread's body (BlueStore::_kv_sync_thread): take ALL
    that queued, commit it as one group, do what is due behind the
    acknowledgements, again. It ends when told to and the queue is
    empty, or when its store is gone."""
    while True:
        with q.cond:
            q.wait_for_work()
            if not q.queued and q.stop:
                return
            group, q.queued = q.queued, []
            q.busy = True
        store = ref()
        try:
            if store is None:
                return
            if group:
                store._commit_group(group)
            store._after_group()
        except Exception as e:
            # a fault of the pipeline itself, past what `_commit_group`
            # takes for a failed sync: the same end, a dead store whose
            # waiters are told, never a thread that is silently gone
            store._fail_group(group, e)
        finally:
            del store, group
            with q.cond:
                q.busy = False
                q.cond.notify_all()


class _Deferred:
    """One context's deferred write: the key of its record in the KV,
    the extents to land, when it was acknowledged, and the units that a
    later transaction freed while it was pending."""

    __slots__ = ("key", "extents", "nbytes", "t_acked", "held")

    def __init__(self, key: str):
        self.key = key
        self.extents: list[tuple[int, int, memoryview]] = []
        self.nbytes = 0
        self.t_acked = 0.0
        self.held: list[tuple[int, int]] = []

    def record(self) -> bytes:
        """The KV value (bluestore_deferred_transaction_t): how many
        extents, the first unit and the count of each, their bytes."""
        return b"".join((
            struct.pack("<I", len(self.extents)),
            *(struct.pack("<QI", unit, count)
              for unit, count, _chunk in self.extents),
            *(chunk for _unit, _count, chunk in self.extents)))


def _record_extents(value: bytes):
    """(unit, bytes) of each extent in a deferred record."""
    n, = struct.unpack_from("<I", value, 0)
    at = 4 + 12 * n
    for i in range(n):
        unit, count = struct.unpack_from("<QI", value, 4 + 12 * i)
        yield unit, value[at:at + count * AU]
        at += count * AU


class BlueStore(ObjectStore):

    csum_block = AU

    def __init__(self, path: str, kv: KeyValueDB | None = None):
        self.path = path
        self.kv = kv if kv is not None else LSMStore(
            os.path.join(path, "db"))
        self._fd: int | None = None         # the block file
        #: a write shorter than this is deferred; an OSD sets it from
        #: `bluestore_prefer_deferred_size`. 0: none is
        self.prefer_deferred_size = INLINE_MAX
        self.deferred_max_bytes = DEFERRED_MAX_BYTES
        self.alloc = BitmapAllocator()
        # per-AU block checksums through the shared Checksummer engine
        # (bluestore_blob_t csum_data at csum_block_size granularity:
        # a single corrupt AU pinpoints instead of failing the whole
        # extent; the engine is the same one the offload service batches
        # for the EC shard csums)
        from ceph_tpu.utils.checksummer import Checksummer
        self.csum = Checksummer("crc32c", AU)
        # test hook: crash after the block file's sync, before the KV
        # batch commit (the txc window the ordering protects)
        self.fail_before_kv = False
        # the commit pipeline. `_lock` guards what both threads touch:
        # the allocator and the overlay of uncommitted state
        self._q = _CommitQueue()
        self._thread: threading.Thread | None = None
        self._lock = threading.Lock()
        self._seq = 0               # contexts queued
        self._groups = 0            # groups taken by the thread
        self._groups_done = 0       # groups whose syncs have returned
        self._done: collections.deque = collections.deque()
        self._tail: _TxnCtx | None = None   # the last context queued
        self._fatal_told = False
        # uncommitted state the reads overlay on the KV, each entry
        # beside the sequence number of the last context that set it:
        # onode key -> (onode | None), collection key -> exists,
        # onode key -> omap overlay (`_TxnCtx.omap_over`'s shape)
        self._pend_onodes: dict[str, tuple[dict | None, int]] = {}
        self._pend_colls: dict[str, tuple[bool, int]] = {}
        self._pend_omap: dict[str, tuple[dict, int]] = {}
        # extents staged and not yet in a group the KV holds, by first
        # unit: what a read takes in place of the block file's bytes
        self._pend_extents: dict[int, memoryview] = {}
        # deferred writes staged and not landed, by the first unit of
        # each extent (under `_lock`), and records made (`_CommitQueue`
        # has the rest)
        self._deferred_by_unit: dict[int, _Deferred] = {}
        self._deferred_seq = 0
        # the allocator as the KV holds it: the commit thread's alone
        self._durable_bits = bytearray()
        self._stats = dict.fromkeys(
            ("txcs", "kv_syncs", "block_syncs", "block_writes",
             "block_bytes_written", "block_bytes_by_ref",
             "csum_bytes_reused", "acks_before_sync", "deferred_ops",
             "deferred_bytes", "deferred_flushes", "deferred_pending_peak",
             "deferred_replayed"), 0)
        # the block file's syncs behind a group's back (the deferred
        # batches'), for the next `bstore_kv_sync` span to count, and
        # the KV's counters as the last span left them
        self._deferred_syncs = 0
        self._kv_seen: dict = {}
        # id -> key, a collection's under the id and an onode's under
        # (cid, oid), and the encodings made since the last context
        # was queued
        self._keys: dict = {}
        self._key_encodes = 0

    # -- lifecycle -----------------------------------------------------------

    def mkfs(self) -> None:
        os.makedirs(self.path, exist_ok=True)
        blk = os.path.join(self.path, "block")
        if not os.path.exists(blk):
            with open(blk, "wb"):
                pass

    def mount(self) -> None:
        if self._fd is not None:
            return
        self.mkfs()
        self.kv.open()
        self._fd = os.open(os.path.join(self.path, "block"), os.O_RDWR)
        blob = self.kv.get(P_SUPER, "freelist")
        if blob and blob[:1] == b"\x78":
            # deflated (a zlib stream's first byte; a bitmap written
            # before this was raw, and begins with a unit's 0 or 1)
            blob = zlib.decompress(blob)
        self.alloc = BitmapAllocator.from_bytes(blob) if blob \
            else BitmapAllocator()
        self._durable_bits = bytearray(self.alloc.bits)
        self._deferred_replay()
        self._kv_seen = dict(self._kv_stats())
        self._q = q = _CommitQueue()
        self._fatal_told = False
        self._thread = threading.Thread(
            target=_kv_sync_thread, args=(weakref.ref(self), q),
            name="bstore-kv-sync", daemon=True)
        self._thread.start()
        weakref.finalize(self, _stop_queue, q)

    def _deferred_replay(self) -> None:
        """At mount: what a kill left under `P_DEFERRED` is written to
        its units, in the order it was queued, and synced; then the
        records go. Run twice (a kill in here) it writes the same bytes
        to the same units."""
        records = list(self.kv.iterate(P_DEFERRED))
        if not records:
            return
        batch = self.kv.transaction()
        for key, value in records:
            for unit, data in _record_extents(value):
                _pwrite_all(self._fd, data, unit * AU)
            batch.rmkey(P_DEFERRED, key)
        os.fdatasync(self._fd)
        self.kv.submit_transaction(batch, sync=True)
        self._stats["deferred_replayed"] += len(records)

    def flush(self) -> None:
        """Wait until everything queued is committed, its callbacks
        have run and every deferred write is on the block file, synced,
        its record gone (ObjectStore::flush). Nothing is closed; a store
        that was flushed and gets nothing more writes nothing more."""
        q = self._q
        with q.cond:
            while q.queued or q.busy or (
                    (q.deferred_ready or q.deferred_done)
                    and q.failed is None):
                q.drain = bool(q.deferred_bytes or q.deferred_done)
                q.cond.notify_all()
                q.cond.wait()
        self._deliver()

    def umount(self) -> None:
        if self._fd is None:
            return
        self.flush()                # drain first, then close
        _stop_queue(self._q)
        self._thread.join()
        self._thread = None
        os.close(self._fd)
        self._fd = None
        self.kv.close()

    def stats(self) -> dict:
        """The pipeline's counters since the store was made: contexts
        committed, groups, syncs of the block file and of the KV,
        extents written and bytes written to each (`block_bytes_by_ref`
        of them from a transaction's own buffer, `csum_bytes_reused`
        checksummed by whoever wrote them), the KV's flushes and
        compactions, `acks_before_sync`: callbacks delivered before
        the group that covers them had finished, which must read 0, and
        the deferred writes: extents and bytes landed (`deferred_ops`,
        `deferred_bytes`, counted among the block file's too), the
        batches they landed in, the most bytes ever pending, and the
        records a mount found and applied."""
        kv = self._kv_stats()
        return {**self._stats,
                "kv_fsyncs": kv.get("fsyncs", 0),
                "kv_bytes_written": kv.get("bytes_written", 0),
                "memtable_flushes": kv.get("memtable_flushes", 0),
                "compactions": kv.get("compactions", 0)}

    def _kv_stats(self) -> dict:
        """The KV's own counters; an engine that keeps none (MemDB)
        reads as zeros."""
        return getattr(self.kv, "stats", {})

    # -- keys and onodes ----------------------------------------------------

    def _cid_key(self, cid: CollectionId) -> str:
        key = self._keys.get(cid)
        return self._remember(cid, _cid_key(cid)) if key is None else key

    def _onode_key(self, cid: CollectionId, oid: Ghobject) -> str:
        key = self._keys.get((cid, oid))
        if key is None:
            key = self._remember(
                (cid, oid), self._cid_key(cid) + "\x01" + _oid_key(oid))
        return key

    def _remember(self, ident, key: str) -> str:
        """An id's key, encoded just now, for every later op and probe
        that names the id (ids are frozen and hash by value)."""
        if len(self._keys) >= KEY_MEMO:
            self._keys.clear()
        self._keys[ident] = key
        self._key_encodes += 1
        return key

    def _onode(self, cid: CollectionId, oid: Ghobject) -> dict | None:
        return self._onode_at(self._onode_key(cid, oid))

    def _onode_at(self, key: str) -> dict | None:
        """The onode as queued: an uncommitted context's, else the
        KV's. Not the caller's to change (`_staged` copies)."""
        pend = self._pend_onodes.get(key)
        if pend is not None:
            return pend[0]
        blob = self.kv.get(P_ONODE, key)
        return None if blob is None else json.loads(blob)

    def _coll_exists(self, cid: CollectionId,
                     ctx: "_TxnCtx | None" = None) -> bool:
        key = self._cid_key(cid)
        if ctx is not None and key in ctx.colls:
            return ctx.colls[key]
        pend = self._pend_colls.get(key)
        if pend is not None:
            return pend[0]
        return self.kv.get(P_COLL, key) is not None

    def _require_coll(self, cid: CollectionId, ctx: "_TxnCtx") -> None:
        """Once a context: its later ops on the collection go on what
        the first found (`RMCOLL` takes the finding back)."""
        key = self._cid_key(cid)
        if key in ctx.colls_found:
            return
        if not self._coll_exists(cid, ctx):
            raise StoreError("ENOENT", f"no collection {cid}")
        ctx.colls_found.add(key)

    def _require_onode(self, cid: CollectionId, oid: Ghobject) -> dict:
        on = self._onode(cid, oid)
        if on is None:
            raise StoreError("ENOENT", f"no object {oid} in {cid}")
        return on

    # -- data path -----------------------------------------------------------

    def _read_extents(self, on: dict) -> bytes:
        extents = on.get("extents")
        if not extents:
            return b""
        out = bytearray()
        with self._lock:
            staged = [self._pend_extents.get(unit)
                      for unit, _count, _crc in extents]
        for (unit, count, crc), chunk in zip(extents, staged):
            if chunk is None:       # retired: the block file has it
                chunk = os.pread(self._fd, count * AU, unit * AU)
            if len(chunk) != count * AU:
                # truncated block file (crash mid-write): same EIO
                # contract as a csum mismatch, so read-repair callers
                # catch it — Checksummer.verify would raise ValueError
                # on the short buffer instead
                raise StoreError(
                    "EIO", f"short read at unit {unit}: "
                           f"{len(chunk)} of {count * AU} bytes")
            if isinstance(crc, list):
                bad = self.csum.verify(chunk,
                                       np.asarray(crc, dtype=np.uint32))
                if bad >= 0:
                    raise StoreError(
                        "EIO", f"csum mismatch at unit {unit} "
                               f"(+{bad} bytes)")
            elif _crc32c(chunk) != crc:
                # whole-extent crc written before the per-AU format
                raise StoreError("EIO",
                                 f"csum mismatch at unit {unit}")
            out.extend(chunk)
        return bytes(out[:on["size"]])

    def _stage_data(self, on: dict, data, ctx: "_TxnCtx",
                    by_ref: bool = False, csums=None) -> None:
        """Replace the onode's data with `data`, bytes-like and nobody's
        to change any more (`by_ref`: the transaction's own buffer).
        The extents are allocated, checksummed and STAGED here, as views
        of `data` on the context; the commit thread writes them: ahead
        of the group's sync, or, where `data` is under the store's
        `prefer_deferred_size`, behind its acknowledgement, from the
        record that the group's KV batch carries. Old extents are
        freed AFTER the batch commits. `csums` is what the write said
        of its blocks (`Transaction.write`): taken for the extents'
        where it is of these very blocks, the buffer staged whole and
        unpadded at one value an allocation unit, and computed here in
        every other case. A read verifies either the same."""
        if "extents" in on:
            ctx.free_after.extend((u, c) for u, c, _ in on["extents"])
        on.pop("extents", None)
        on["size"] = len(data)
        if not len(data):
            return
        deferred = len(data) < self.prefer_deferred_size
        pad = (-len(data)) % AU
        if pad:
            data, by_ref = b"".join((data, bytes(pad))), False
        view = memoryview(data).toreadonly()
        given = _unit_csums(csums, len(view) // AU) if by_ref else None
        if deferred:
            self._reserve_deferred(len(view), ctx)
        staged = []
        off = 0
        with self._lock:
            # a group that failed has given its units back by now, and
            # the KV's log may still name them: nothing is staged over
            # them (`_fail_group` sets `failed` before it frees)
            self._refuse_if_failed()
            got = self.alloc.allocate(len(view) // AU)
            ctx.allocated.extend(got)
            for unit, count in got:
                chunk = view[off:off + count * AU]
                self._pend_extents[unit] = chunk
                staged.append((unit, count, chunk))
                off += count * AU
                if deferred:
                    self._deferred_by_unit[unit] = ctx.deferred
        if deferred:
            ctx.deferred.extents.extend(staged)
            ctx.deferred.nbytes += len(view)
        else:
            ctx.block_writes.extend(staged)
        if given is None:
            on["extents"] = [
                [unit, count, self.csum.calculate(chunk).tolist()]
                for unit, count, chunk in staged]
        else:       # a run's values are those of its place in the buffer
            on["extents"], at = [], 0
            for unit, count, _chunk in staged:
                on["extents"].append([unit, count, given[at:at + count]])
                at += count
            ctx.csum_reused_bytes += len(view)
        ctx.block_bytes += len(view)
        if by_ref:
            ctx.by_ref_bytes += len(view)

    def _reserve_deferred(self, nbytes: int, ctx: "_TxnCtx") -> None:
        """Take `nbytes` of the bound on deferred bytes for the context,
        waiting, on the caller's thread, while others hold too much of
        it (the throttle `queue_transactions` blocks in): the commit
        thread is asked to land what it has."""
        q = self._q
        if ctx.deferred is None:
            self._deferred_seq += 1
            ctx.deferred = _Deferred(f"{self._deferred_seq:016x}")
        mine = ctx.deferred.nbytes
        t0 = None
        with q.cond:
            while q.deferred_bytes > mine and q.failed is None and \
                    q.deferred_bytes + nbytes > self.deferred_max_bytes:
                if t0 is None:
                    t0 = time.perf_counter()
                q.drain = True
                q.cond.notify_all()
                q.cond.wait()
            if t0 is not None:
                ctx.deferred_wait_us += (time.perf_counter() - t0) * 1e6
            q.deferred_bytes += nbytes
            peak = self._stats["deferred_pending_peak"]
            self._stats["deferred_pending_peak"] = max(peak,
                                                       q.deferred_bytes)

    def _unstage(self, ctxs: "list[_TxnCtx]", free: bool) -> None:
        """These contexts' staged extents leave the reads' map: the
        block file has them (`_retire`; a deferred one stays until its
        batch has landed), or nothing ever will, and then their units go
        back as well (`free`). Under `_lock`."""
        for ctx in ctxs:
            for unit, _count, _chunk in ctx.block_writes:
                self._pend_extents.pop(unit, None)
            ctx.block_writes = []
            if not free:
                continue
            if ctx.deferred is not None:
                for unit, _count, _chunk in ctx.deferred.extents:
                    self._pend_extents.pop(unit, None)
                    self._deferred_by_unit.pop(unit, None)
                with self._q.cond:
                    self._q.deferred_bytes -= ctx.deferred.nbytes
                    self._q.cond.notify_all()
                ctx.deferred = None
            self.alloc.free(ctx.allocated)

    def _free(self, extents: list[tuple[int, int]]) -> None:
        """Units that a committed transaction let go return to the
        allocator, but for those a deferred write is still to land on:
        handed out now, they would be written twice, the older bytes
        last. They go back when it has landed. Under `_lock`."""
        for unit, count in extents:
            pending = self._deferred_by_unit.get(unit)
            if pending is None:
                self.alloc.free([(unit, count)])
            else:
                pending.held.append((unit, count))

    # -- the commit pipeline -------------------------------------------------

    def queue_transaction(self, txn: Transaction) -> None:
        """PREPARE on the caller's thread, queue the context, return:
        the transaction is readable now (`on_applied` fires before the
        return) and durable when `on_commit` fires, which the commit
        thread hands back to the caller's loop. The span and the
        histogram around this call (`store_commit`) are what the
        caller's thread pays, not the commit. With no running loop the
        call waits for the commit, and raises what it raised."""
        q = self._q
        self._refuse_if_failed()
        ctx = _TxnCtx(self.kv.transaction())
        try:
            for op in txn.ops:
                self._apply_op(op, ctx)
        except BaseException:
            # all-or-nothing: nothing was queued, so units allocated
            # by earlier ops of this txn must return to the allocator
            with self._lock:
                self._unstage([ctx], free=True)
            raise
        for key, on in ctx.onodes.items():
            if on is None:
                ctx.batch.rmkey(P_ONODE, key)
            else:
                ctx.batch.set(P_ONODE, key, json.dumps(on).encode())
        if ctx.deferred is not None:
            ctx.batch.set(P_DEFERRED, ctx.deferred.key,
                          ctx.deferred.record())
        ctx.n_ops = len(txn.ops)
        ctx.on_applied, ctx.on_commit = txn.on_applied, txn.on_commit
        try:
            ctx.loop = asyncio.get_running_loop()
        except RuntimeError:
            ctx.loop = None
        with self._lock:
            self._seq = seq = self._seq + 1
            ctx.seq = seq
            self._publish(ctx, seq)
        ctx.key_encodes, self._key_encodes = self._key_encodes, 0
        ctx.state = "queued"
        ctx.t_queued = time.perf_counter()
        with q.cond:
            q.queued.append(ctx)
            q.cond.notify_all()
        if ctx.loop is not None:
            for fn in ctx.on_applied:
                fn()
            return
        with q.cond:
            while ctx.state not in _SETTLED:
                q.cond.wait()
        self._deliver()
        if ctx.error is not None:
            raise ctx.error

    @property
    def failed(self) -> BaseException | None:
        return self._q.failed

    def _refuse_if_failed(self) -> None:
        if self._q.failed is not None:
            raise StoreError("EIO", f"store failed at a commit: "
                                    f"{self._q.failed!r}")

    def flush_commit(self, fn) -> None:
        """`fn()` once everything queued so far is durable: now, where
        nothing is in flight, else among the last queued context's
        `on_commit`s (CollectionHandle::flush_commit; one thread
        commits the whole store in queue order, so the last covers
        the rest)."""
        last = self._tail
        if last is None:
            fn()
        else:   # its own list: the transaction's is the caller's
            last.on_commit = [*last.on_commit, fn]

    def _publish(self, ctx: "_TxnCtx", seq: int) -> None:
        """Lay a prepared context over the KV for the reads. Under
        `_lock`."""
        self._tail = ctx
        for key, on in ctx.onodes.items():
            self._pend_onodes[key] = (on, seq)
        for key, exists in ctx.colls.items():
            self._pend_colls[key] = (exists, seq)
        for key, over in ctx.omap_over.items():
            old = self._pend_omap.get(key)
            if old is not None and _CLEAR not in over:
                over = {**old[0], **over}
            self._pend_omap[key] = (over, seq)

    def _retire(self, group: "list[_TxnCtx]") -> None:
        """The KV holds the group now: its frees reach the allocator
        (`_free`), its extents are read from the block file, the
        deferred ones apart, and what it laid over the KV goes unless a
        later context has laid its own there since. Under `_lock`."""
        hi = group[-1].seq
        self._unstage(group, free=False)
        for ctx in group:
            self._free(ctx.free_after)
            for pend, keys in ((self._pend_onodes, ctx.onodes),
                               (self._pend_colls, ctx.colls),
                               (self._pend_omap, ctx.omap_over)):
                for key in keys:
                    if key in pend and pend[key][1] <= hi:
                        del pend[key]

    def _commit_group(self, group: "list[_TxnCtx]") -> None:
        """On the commit thread: the contexts' staged extents written
        in queue order, the deferred ones apart, one sync of the block
        file if there were any, one synced KV batch for all the contexts
        (the freelist key once; the deferred records; the removal of the
        records that have landed), the frees, then the callbacks handed
        back in queue order."""
        if self._q.failed is not None:
            # prepared while the group before it failed: nothing
            # commits behind a hole
            self._fail_group(group, self._q.failed)
            return
        t0 = time.perf_counter()
        self._groups = seqno = self._groups + 1
        for ctx in group:
            ctx.group, ctx.t_taken = seqno, t0
        deferred = [ctx.deferred for ctx in group
                    if ctx.deferred is not None]
        block_bytes = sum(ctx.block_bytes for ctx in group)
        direct_bytes = block_bytes - sum(d.nbytes for d in deferred)
        by_ref_bytes = sum(ctx.by_ref_bytes for ctx in group)
        csum_reused = sum(ctx.csum_reused_bytes for ctx in group)
        direct_writes = sum(len(ctx.block_writes) for ctx in group)
        block_writes = direct_writes + sum(len(d.extents) for d in deferred)
        freelist_bytes = 0
        try:
            # data before metadata: the txc ordering (BlueStore.cc
            # _txc_state_proc) — a crash in here leaves old onodes
            # valid, the new units named by nothing durable
            for ctx in group:
                for unit, _count, chunk in ctx.block_writes:
                    _pwrite_all(self._fd, chunk, unit * AU)
            tw = time.perf_counter()
            if direct_bytes:
                os.fdatasync(self._fd)
            t1 = time.perf_counter()
            for ctx in group:
                ctx.state = "block_synced"
            if self.fail_before_kv:
                raise SimulatedCrash(
                    "crash between data write and KV commit")
            batch = self.kv.transaction()
            for ctx in group:
                batch.ops.extend(ctx.batch.ops)
            # the persisted bitmap takes the group's allocations and
            # returns its frees atomically with the metadata that
            # started and stopped referencing them (the FreelistManager
            # role); units of contexts not in the group are not in it
            bits = self._durable_bits
            if any(ctx.allocated or ctx.free_after for ctx in group):
                bits = bytearray(bits)
                for ctx in group:
                    for unit, count in ctx.allocated:
                        if unit + count > len(bits):
                            bits.extend(bytes(unit + count - len(bits)))
                        bits[unit:unit + count] = b"\x01" * count
                for ctx in group:
                    for unit, count in ctx.free_after:
                        bits[unit:unit + count] = bytes(count)
                # deflated: a byte a unit is nearly all ones on a device
                # that fills, and raw it was most of every group's log
                # record; `zlib` works without the GIL
                value = zlib.compress(bits, 1)
                batch.set(P_SUPER, "freelist", value)
                freelist_bytes = len(value)
            # records whose extents the block file holds, synced: gone
            # with this batch, or found again and applied again
            removed = self._q.deferred_done
            for key in removed:
                batch.rmkey(P_DEFERRED, key)
            if batch.ops:
                self.kv.submit_transaction(batch, sync=True)
            t2 = time.perf_counter()
        except Exception as e:
            self._fail_group(group, e)
            return
        self._durable_bits = bits
        with self._lock:
            self._retire(group)
        # acknowledged from here on: the deferred ones are the thread's
        # to land, in this order
        for d in deferred:
            d.t_acked = t2
        with self._q.cond:
            self._q.deferred_done = []
            self._q.deferred_ready.extend(deferred)
            self._q.deferred_ops += block_writes - direct_writes
        kv1 = self._kv_stats()
        kv0, self._kv_seen = self._kv_seen, dict(kv1)
        behind, self._deferred_syncs = self._deferred_syncs, 0
        st = self._stats
        st["txcs"] += len(group)
        st["kv_syncs"] += bool(batch.ops)
        st["block_syncs"] += bool(direct_bytes)
        st["block_writes"] += direct_writes
        st["block_bytes_written"] += direct_bytes
        st["block_bytes_by_ref"] += by_ref_bytes
        st["csum_bytes_reused"] += csum_reused
        perf = self.commit_perf
        if perf is not None:
            perf.hist_add("store_kv_sync_us", (t2 - t0) * 1e6)
        if tracer.active():
            tracer.record_span(
                "bstore_kv_sync", t0, (t2 - t0) * 1e6,
                {"group": seqno, "txcs": len(group),
                 "block_synced": int(bool(direct_bytes)) + behind,
                 "block_writes": block_writes,
                 "block_write_us": (tw - t0) * 1e6,
                 "block_sync_us": (t1 - t0) * 1e6,
                 "kv_submit_us": (t2 - t1) * 1e6,
                 "kv_fsyncs": kv1.get("fsyncs", 0) - kv0.get("fsyncs", 0),
                 "block_bytes": block_bytes,
                 "kv_bytes": kv1.get("bytes_written", 0)
                 - kv0.get("bytes_written", 0),
                 "freelist_bytes": freelist_bytes,
                 "deferred_in": len(deferred),
                 "deferred_removed": len(removed)},
                getattr(self, "name", type(self).__name__))
        for ctx in group:
            ctx.block_write_us = (tw - t0) * 1e6
            ctx.block_sync_us = (t1 - t0) * 1e6
            ctx.kv_submit_us = (t2 - t1) * 1e6
            ctx.t_synced = t2
            ctx.state = "kv_submitted"
        self._groups_done = seqno
        self._hand_back(group)

    def _after_group(self) -> None:
        """On the commit thread, with a group's acknowledgements gone
        (or woken with none to commit): land the deferred writes if a
        batch is due, and let the KV flush or compact if that is due.
        Neither keeps an acknowledgement waiting that was ready."""
        q = self._q
        if q.failed is not None:
            return
        if q.deferred_ready and (
                q.drain or q.deferred_ops >= DEFERRED_BATCH_OPS
                or 2 * q.deferred_bytes >= self.deferred_max_bytes
                or time.perf_counter() - q.deferred_ready[0].t_acked
                >= DEFERRED_MAX_AGE_S):
            self._deferred_flush()
        gone = q.drain and bool(q.deferred_done)
        if gone:
            # someone waits for the store to be still (`flush`): the
            # records go now, in a batch of their own
            batch = self.kv.transaction()
            for key in q.deferred_done:
                batch.rmkey(P_DEFERRED, key)
            self.kv.submit_transaction(batch, sync=True)
        with q.cond:
            if gone:
                q.deferred_done = []
            if not q.deferred_ready and not q.deferred_done:
                q.drain = False
        self.kv.maintain()

    def _deferred_flush(self) -> None:
        """Write every acknowledged deferred extent to its units, in the
        order acknowledged, and sync the block file once
        (_deferred_submit_unlock, _deferred_aio_finish). From then on
        reads take them from the block file, the units that were freed
        under them are free, and their records are the next KV batch's
        to remove."""
        q = self._q
        landing = q.deferred_ready
        t0 = time.perf_counter()
        ops = nbytes = 0
        for d in landing:
            for unit, _count, chunk in d.extents:
                _pwrite_all(self._fd, chunk, unit * AU)
            ops += len(d.extents)
            nbytes += d.nbytes
        tw = time.perf_counter()
        os.fdatasync(self._fd)
        ts = time.perf_counter()
        with self._lock:
            for d in landing:
                for unit, _count, _chunk in d.extents:
                    self._pend_extents.pop(unit, None)
                    self._deferred_by_unit.pop(unit, None)
                self.alloc.free(d.held)
        with q.cond:
            q.deferred_ready = []
            q.deferred_ops -= ops
            q.deferred_bytes -= nbytes
            q.deferred_done = q.deferred_done + [d.key for d in landing]
            pending = q.deferred_bytes
            q.cond.notify_all()
        st = self._stats
        st["deferred_ops"] += ops
        st["deferred_bytes"] += nbytes
        st["deferred_flushes"] += 1
        st["block_syncs"] += 1
        st["block_writes"] += ops
        st["block_bytes_written"] += nbytes
        self._deferred_syncs += 1
        if tracer.active():
            lags = [(ts - d.t_acked) * 1e6 for d in landing]
            tracer.record_span(
                "bstore_deferred_flush", t0, (ts - t0) * 1e6,
                {"ops": ops, "bytes": nbytes, "records": len(landing),
                 "write_us": (tw - t0) * 1e6, "sync_us": (ts - tw) * 1e6,
                 "oldest_lag_us": lags[0],
                 "median_lag_us": statistics.median(lags),
                 "pending_bytes": pending},
                getattr(self, "name", type(self).__name__))

    def _fail_group(self, group: "list[_TxnCtx]", e: BaseException) -> None:
        """A block write, a sync or the KV failed (ENOSPC, EIO, a test's
        crash hook): no context of the group, and none queued behind
        it, commits or calls back; their units return to the allocator
        and their staged extents are let go, the frees they staged
        never happen, and the store takes no more transactions
        (upstream aborts the OSD; here its sub-op waits time out and
        the clients resend)."""
        q = self._q
        with q.cond:
            q.failed = e
            group = group + q.queued
            q.queued = []
        with self._lock:
            self._unstage(group, free=True)
        for ctx in group:
            ctx.error, ctx.state = e, "failed"
        with q.cond:
            q.cond.notify_all()     # a `prepare` at the bound, a `flush`
        if self._fatal_told:
            return
        self._fatal_told = True
        dout("bluestore", 0, f"{self.path}: commit failed, store is dead: "
                             f"{type(e).__name__} {e}")
        loop = next((c.loop for c in group if c.loop is not None), None)
        if loop is not None and self.on_fatal is not None:
            try:
                loop.call_soon_threadsafe(self.on_fatal, e)
            except RuntimeError:
                pass        # the loop is closed: nobody is left to tell

    def _hand_back(self, group: "list[_TxnCtx]") -> None:
        """Committed contexts go to `_done` in queue order; each loop
        that queued some is told once to deliver."""
        self._done.extend(group)
        loops = []
        for ctx in group:
            if ctx.loop is not None and ctx.loop not in loops:
                loops.append(ctx.loop)
        for loop in loops:
            try:
                loop.call_soon_threadsafe(self._deliver)
            except RuntimeError:
                pass        # the loop is closed: a flush delivers, or none

    def _deliver(self) -> None:
        """Run the callbacks of what the thread handed back, in queue
        order: on the loop that queued them, or on the thread that
        waits in `queue_transaction` or `flush`."""
        traced = tracer.active()
        while True:
            try:
                ctx = self._done.popleft()
            except IndexError:
                return
            now = time.perf_counter()
            ran_ahead = ctx.group > self._groups_done
            self._stats["acks_before_sync"] += ran_ahead
            if traced:
                tracer.record_span(
                    "bstore_txc", ctx.t0, (now - ctx.t0) * 1e6,
                    {"prepare_us": (ctx.t_queued - ctx.t0) * 1e6,
                     "queued_us": (ctx.t_taken - ctx.t_queued) * 1e6,
                     "block_sync_us": ctx.block_sync_us,
                     "kv_submit_us": ctx.kv_submit_us,
                     "deliver_us": (now - ctx.t_synced) * 1e6,
                     "block_write_us": ctx.block_write_us,
                     "ops": ctx.n_ops, "bytes": ctx.block_bytes,
                     "by_ref_bytes": ctx.by_ref_bytes,
                     "deferred_bytes": ctx.deferred_bytes,
                     "deferred_wait_us": ctx.deferred_wait_us,
                     "csum_reused_bytes": ctx.csum_reused_bytes,
                     "key_encodes": ctx.key_encodes,
                     "group": ctx.group, "ran_ahead": bool(ran_ahead)},
                    getattr(self, "name", type(self).__name__))
            ctx.state = "done"
            if self._tail is ctx:
                self._tail = None
            fns = ctx.on_commit if ctx.loop is not None \
                else [*ctx.on_applied, *ctx.on_commit]
            for fn in fns:
                try:
                    fn()
                except Exception as e:
                    dout("bluestore", 0, f"{self.path}: a commit callback "
                                         f"raised {type(e).__name__} {e}")

    def _staged(self, ctx: "_TxnCtx", key: str) -> dict | None:
        """The onode for this context to change: its own, else a copy
        of what is queued or committed."""
        if key in ctx.onodes:
            return ctx.onodes[key]
        pend = self._pend_onodes.get(key)
        if pend is None:
            return self._onode_at(key)          # parsed anew: ours
        on = pend[0]
        # a queued context's: its extents list is replaced, never
        # changed in place, so one level of copy is enough
        return None if on is None else \
            {**on, "attrs": dict(on.get("attrs", {}))}

    def _apply_op(self, op: tuple, ctx: "_TxnCtx") -> None:
        kind = op[0]
        if kind == Op.MKCOLL:
            cid = op[1]
            if self._coll_exists(cid, ctx):
                raise StoreError("EEXIST", f"collection {cid} exists")
            ckey = self._cid_key(cid)
            ctx.batch.set(P_COLL, ckey, b"1")
            ctx.colls[ckey] = True
            return
        if kind == Op.RMCOLL:
            cid = op[1]
            self._require_coll(cid, ctx)
            ckey = self._cid_key(cid)
            prefix = ckey + "\x01"
            live = {self._onode_key(cid, gh)
                    for gh in self.collection_list(cid)}
            for k, on in ctx.onodes.items():
                if not k.startswith(prefix):
                    continue
                if on is None:
                    live.discard(k)
                else:
                    live.add(k)          # created earlier in THIS txn
            if live:
                raise StoreError("ENOTEMPTY",
                                 f"collection {cid} not empty")
            ctx.batch.rmkey(P_COLL, ckey)
            ctx.colls[ckey] = False
            ctx.colls_found.discard(ckey)
            return
        # an op on an object: its key is resolved here, once, and every
        # step below works on the key
        cid, oid = op[1], op[2]
        key = self._onode_key(cid, oid)

        if kind == Op.TOUCH:
            self._require_coll(cid, ctx)
            if self._staged(ctx, key) is None:
                ctx.onodes[key] = {"size": 0, "attrs": {}}
            return
        if kind == Op.WRITE:
            self._require_coll(cid, ctx)
            offset, data, csums = op[3], op[4], op[5]
            on = self._staged(ctx, key) or \
                {"size": 0, "attrs": {}}
            if offset == 0 and on["size"] <= len(data):
                # the object replaced whole (every push and write_full):
                # `Transaction.write` made the buffer the store's, and
                # its blocks' csums, where it brought some, are of what
                # is staged
                self._stage_data(on, data, ctx, by_ref=True, csums=csums)
            else:
                cur = bytearray(self._read_staged(on))
                if len(cur) < offset:
                    cur.extend(b"\x00" * (offset - len(cur)))
                cur[offset:offset + len(data)] = data
                self._stage_data(on, cur, ctx)
            ctx.onodes[key] = on
            return
        if kind == Op.ZERO:
            self._require_coll(cid, ctx)
            offset, length = op[3], op[4]
            on = self._staged(ctx, key) or \
                {"size": 0, "attrs": {}}
            cur = bytearray(self._read_staged(on))
            if len(cur) < offset + length:
                cur.extend(b"\x00" * (offset + length - len(cur)))
            cur[offset:offset + length] = b"\x00" * length
            self._stage_data(on, cur, ctx)
            ctx.onodes[key] = on
            return
        if kind == Op.TRUNCATE:
            self._require_coll(cid, ctx)
            size = op[3]
            on = self._staged(ctx, key) or \
                {"size": 0, "attrs": {}}
            cur = bytearray(self._read_staged(on))
            if len(cur) < size:
                cur.extend(b"\x00" * (size - len(cur)))
            else:
                del cur[size:]
            self._stage_data(on, cur, ctx)
            ctx.onodes[key] = on
            return
        if kind == Op.REMOVE:
            on = self._require_staged(ctx, key, cid, oid)
            if "extents" in on:
                ctx.free_after.extend((u, c) for u, c, _ in on["extents"])
            ctx.onodes[key] = None
            ctx.batch.rmkeys_by_prefix(P_OMAP + "\x01" + key)
            ctx.omap_over[key] = {_CLEAR: None}
            return
        if kind == Op.SETATTRS:
            self._require_coll(cid, ctx)
            on = self._staged(ctx, key) or \
                {"size": 0, "attrs": {}}
            on.setdefault("attrs", {}).update(
                {k: v.decode("latin1") for k, v in op[3].items()})
            ctx.onodes[key] = on
            return
        if kind == Op.RMATTR:
            on = self._require_staged(ctx, key, cid, oid)
            on.get("attrs", {}).pop(op[3], None)
            ctx.onodes[key] = on
            return
        if kind == Op.CLONE:
            src, dkey = op[2], self._onode_key(cid, op[3])
            son = self._staged(ctx, key)
            if son is None:
                raise StoreError("ENOENT", f"no object {src}")
            data = self._read_staged(son)
            don = {"size": 0, "attrs": dict(son.get("attrs", {}))}
            old = self._staged(ctx, dkey)
            if old is not None and "extents" in old:
                ctx.free_after.extend((u, c)
                                      for u, c, _ in old["extents"])
            self._stage_data(don, data, ctx)
            ctx.onodes[dkey] = don
            # omap clones with the object (MemStore does the same);
            # the CLEAR sentinel hides dst's committed keys from later
            # same-txn readers (replace, never merge)
            okeys = dict(self._omap_staged(ctx, key))
            pre_dst = P_OMAP + "\x01" + dkey
            ctx.batch.rmkeys_by_prefix(pre_dst)
            over = {_CLEAR: None}
            for k, v in okeys.items():
                ctx.batch.set(pre_dst, k, v)
                over[k] = v
            ctx.omap_over[dkey] = over
            return
        if kind == Op.CLONE_RANGE:
            src, src_off, length, dst_off = op[2], op[4], op[5], op[6]
            dkey = self._onode_key(cid, op[3])
            son = self._staged(ctx, key)
            if son is None:
                raise StoreError("ENOENT", f"no object {src}")
            sdata = self._read_staged(son)[src_off:src_off + length]
            don = self._staged(ctx, dkey) or \
                {"size": 0, "attrs": {}}
            cur = bytearray(self._read_staged(don))
            if len(cur) < dst_off:
                cur.extend(b"\x00" * (dst_off - len(cur)))
            cur[dst_off:dst_off + len(sdata)] = sdata
            self._stage_data(don, cur, ctx)
            ctx.onodes[dkey] = don
            return
        if kind == Op.COLL_MOVE_RENAME:
            new_cid, new_key = op[3], self._onode_key(op[3], op[4])
            on = self._staged(ctx, key)
            if on is None:
                raise StoreError("ENOENT", f"no object {oid}")
            self._require_coll(new_cid, ctx)
            okeys = dict(self._omap_staged(ctx, key))
            dst_old = self._staged(ctx, new_key)
            if dst_old is not None and "extents" in dst_old:
                # replaced destination: its space must return
                ctx.free_after.extend((u, c)
                                      for u, c, _ in dst_old["extents"])
            ctx.onodes[key] = None
            ctx.batch.rmkeys_by_prefix(P_OMAP + "\x01" + key)
            ctx.omap_over[key] = {_CLEAR: None}
            ctx.onodes[new_key] = on
            pre = P_OMAP + "\x01" + new_key
            ctx.batch.rmkeys_by_prefix(pre)    # replace, never merge
            over = {_CLEAR: None}
            for k, v in okeys.items():
                ctx.batch.set(pre, k, v)
                over[k] = v
            ctx.omap_over[new_key] = over
            return
        if kind == Op.OMAP_SETKEYS:
            self._require_coll(cid, ctx)
            on = self._staged(ctx, key) or \
                {"size": 0, "attrs": {}}
            ctx.onodes[key] = on
            pre = P_OMAP + "\x01" + key
            over = ctx.omap_over.setdefault(key, {})
            for k, v in op[3].items():
                ctx.batch.set(pre, k, v)
                over[k] = v
            return
        if kind == Op.OMAP_RMKEYS:
            self._require_coll(cid, ctx)
            on = self._staged(ctx, key) or \
                {"size": 0, "attrs": {}}
            ctx.onodes[key] = on
            pre = P_OMAP + "\x01" + key
            over = ctx.omap_over.setdefault(key, {})
            for k in op[3]:
                ctx.batch.rmkey(pre, k)
                over[k] = None
            return
        if kind == Op.OMAP_CLEAR:
            ctx.batch.rmkeys_by_prefix(P_OMAP + "\x01" + key)
            ctx.omap_over[key] = {_CLEAR: None}
            return
        raise StoreError("EINVAL", f"unknown op {kind}")

    def _require_staged(self, ctx: "_TxnCtx", key: str,
                        cid: CollectionId, oid: Ghobject) -> dict:
        on = self._staged(ctx, key)
        if on is None:
            raise StoreError("ENOENT", f"no object {oid} in {cid}")
        return on

    def _read_staged(self, on: dict) -> bytes:
        return self._read_extents(on)

    def _omap_staged(self, ctx: "_TxnCtx", key: str) -> dict[str, bytes]:
        staged_off = key in ctx.onodes and ctx.onodes[key] is None
        base = self._omap_view(key) \
            if self._onode_at(key) is not None and not staged_off else {}
        return _overlaid(base, ctx.omap_over.get(key, {}))

    def _omap_view(self, key: str) -> dict[str, bytes]:
        """An onode's omap as queued: the KV's with the uncommitted
        contexts' overlay on it. The overlay is read FIRST, here and in
        every read: the commit thread puts a group into the KV and only
        then takes its overlay away, so an overlay found may lie over
        either KV (laying it twice changes nothing) and one not found
        has left a KV that holds it."""
        pend = self._pend_omap.get(key)
        base = dict(self.kv.iterate(P_OMAP + "\x01" + key))
        return base if pend is None else _overlaid(base, pend[0])

    # -- reads ---------------------------------------------------------------

    def list_collections(self) -> list[CollectionId]:
        with self._lock:
            pend = {k: v[0] for k, v in self._pend_colls.items()}
        have = {k for k, _ in self.kv.iterate(P_COLL)}
        have = (have | {k for k, e in pend.items() if e}) \
            - {k for k, e in pend.items() if not e}
        return sorted(_cid_from(k) for k in have)

    def collection_exists(self, cid: CollectionId) -> bool:
        return self._coll_exists(cid)

    def collection_list(self, cid: CollectionId,
                        start: Ghobject | None = None,
                        max_count: int = 2 ** 31) -> list[Ghobject]:
        prefix = self._cid_key(cid) + "\x01"
        with self._lock:
            pend = [(k, v[0] is not None)
                    for k, v in self._pend_onodes.items()
                    if k.startswith(prefix)]
        have = set()
        for k, _ in self.kv.iterate(P_ONODE, start=prefix):
            if not k.startswith(prefix):
                break                    # keys are ordered: prefix done
            have.add(k)
        for k, exists in pend:
            (have.add if exists else have.discard)(k)
        out = sorted(_oid_from(k[len(prefix):]) for k in have)
        if start is not None:
            out = [o for o in out if o > start]
        return out[:max_count]

    def exists(self, cid: CollectionId, oid: Ghobject) -> bool:
        return self._onode(cid, oid) is not None

    def stat(self, cid: CollectionId, oid: Ghobject) -> dict:
        on = self._require_onode(cid, oid)
        return {"size": on["size"]}

    def read(self, cid: CollectionId, oid: Ghobject, offset: int = 0,
             length: int | None = None) -> bytes:
        on = self._require_onode(cid, oid)
        data = self._read_extents(on)
        if length is None:
            return data[offset:]
        return data[offset:offset + length]

    def getattr(self, cid: CollectionId, oid: Ghobject,
                name: str) -> bytes:
        on = self._require_onode(cid, oid)
        if name not in on.get("attrs", {}):
            raise StoreError("ENODATA", f"no attr {name} on {oid}")
        return on["attrs"][name].encode("latin1")

    def getattrs(self, cid: CollectionId,
                 oid: Ghobject) -> dict[str, bytes]:
        on = self._require_onode(cid, oid)
        return {k: v.encode("latin1")
                for k, v in on.get("attrs", {}).items()}

    def omap_get(self, cid: CollectionId,
                 oid: Ghobject) -> dict[str, bytes]:
        self._require_onode(cid, oid)
        return self._omap_view(self._onode_key(cid, oid))

    def omap_get_values(self, cid: CollectionId, oid: Ghobject,
                        keys) -> dict[str, bytes]:
        omap = self.omap_get(cid, oid)
        return {k: omap[k] for k in keys if k in omap}


def _unit_csums(csums, n_units: int) -> list[int] | None:
    """What a write said of its blocks (`Transaction.write`'s `csums`)
    as the csums of `n_units` staged allocation units, or None where it
    does not say that: no word, another block size, another count, or
    values that are no 32-bit numbers."""
    if csums is None:
        return None
    try:
        block, values = csums
        if block != AU or len(values) != n_units:
            return None
        return np.asarray(values, dtype=np.uint32).tolist()
    except (TypeError, ValueError, OverflowError):
        return None


def _overlaid(base: dict[str, bytes], over: dict) -> dict[str, bytes]:
    """`base` with an omap overlay on it: `_CLEAR` empties it, a key set
    to None goes, any other is set."""
    if _CLEAR in over:
        base = {}
    for k, v in over.items():
        if k == _CLEAR:
            continue
        if v is None:
            base.pop(k, None)
        else:
            base[k] = v
    return base


class _TxnCtx:
    """A transaction context (upstream's TransContext): what `prepare`
    staged, onode edits + omap overlay + extents to write, ahead of the
    sync or behind the acknowledgement, + frees to make once it has
    committed + the slice of the group's KV batch, and where the context
    stands in the pipeline (`_SETTLED`'s comment) with the clock at
    each step."""

    def __init__(self, batch: KVTransaction):
        self.batch = batch
        self.onodes: dict[str, dict | None] = {}
        self.colls: dict[str, bool] = {}    # made (True), removed (False)
        self.colls_found: set[str] = set()  # met by an op, and there
        self.omap_over: dict[str, dict] = {}
        self.free_after: list[tuple[int, int]] = []
        self.allocated: list[tuple[int, int]] = []
        #: (unit, count, read-only view): for the commit thread to write
        self.block_writes: list[tuple[int, int, memoryview]] = []
        #: the context's deferred write, if it stages one: its extents
        #: are the commit thread's to write once this is acknowledged
        self.deferred: _Deferred | None = None
        self.deferred_wait_us = 0.0         # `prepare` waited at the bound
        self.block_bytes = 0                # staged for the block file
        self.by_ref_bytes = 0               # of them, the txn's own buffer
        self.csum_reused_bytes = 0          # of them, csums came with it
        self.key_encodes = 0                # the store's, up to this one
        self.n_ops = 0
        self.state = "prepare"
        self.error: BaseException | None = None
        self.seq = 0                        # in the store's queue order
        self.group = 0                      # the group that covers it
        self.loop = None                    # the caller's, to call back on
        self.on_applied: list = []
        self.on_commit: list = []
        self.t0 = time.perf_counter()       # prepare began
        self.t_queued = self.t_taken = self.t_synced = 0.0
        self.block_write_us = self.block_sync_us = self.kv_submit_us = 0.0

    @property
    def deferred_bytes(self) -> int:
        """Of `block_bytes`, those acknowledged from the KV sync alone."""
        return self.deferred.nbytes if self.deferred is not None else 0
