"""MemStore: in-memory ObjectStore (the reference test double,
src/os/memstore/MemStore.h:30).

Transactions are validated then applied under the store lock; validation
failures reject the WHOLE transaction with no partial effects (the
all-or-nothing contract queue_transaction promises). on_applied fires
when the data is readable, on_commit immediately after (memory is always
"durable" here) — same ordering the OSD relies on.

A commit costs what it changes. An object's data is either an IMMUTABLE
buffer the store was given (`bytes`, or a read-only `memoryview`: what
`Transaction.write` lets through by reference) or a PRIVATE `bytearray`.
A write at offset 0 onto an empty object keeps the buffer it is given;
any other mutation of an immutable object first makes it private with
one copy. A read of an immutable object is a read-only window on it; of
a private one a `bytes` copy, because a view of a `bytearray` would make
its next resize raise `BufferError`. A replaced object gets a NEW
buffer, so a window handed out before shows the bytes it was read at
for as long as anyone holds it. The buffers the store keeps (rx bodies
of the messenger among them) are never resized, pooled or recycled by
whoever made them: keeping one is owning it. Upstream's
BufferlistObject does the same with `claim_append` / `substr_of`.
"""
from __future__ import annotations

import threading
import time
from typing import Iterable

from ceph_tpu.objectstore.store import (ObjectStore, Op, StoreError,
                                        Transaction)
from ceph_tpu.objectstore.types import CollectionId, Ghobject
from ceph_tpu.utils import copytrack
from ceph_tpu.utils.perf_counters import PerfCounters


def _adoptable(data) -> bool:
    """Whether the store may keep `data` itself: immutable, and not a
    sliver of something larger. A view that covers under a quarter of
    the buffer beneath it is copied instead, so that a small chunk never
    pins a batch envelope and what a deleted neighbour leaves pinned is
    at most four times an object's size. A quarter and not a half: two
    512 KiB sub-op writes fit one 1 MiB envelope, each a frame's
    overhead under half of it, and both are kept."""
    if isinstance(data, bytes):
        return True
    return (isinstance(data, memoryview) and data.readonly
            and len(data) * 4 >= len(data.obj))


class _Object:
    __slots__ = ("data", "xattrs", "omap", "mtime")

    def __init__(self):
        #: bytes or a read-only memoryview (immutable, shared with
        #: whoever reads or clones it) or a bytearray (private)
        self.data = b""
        self.xattrs: dict[str, bytes] = {}
        self.omap: dict[str, bytes] = {}
        self.mtime = time.time()

    def clone(self) -> "_Object":
        out = _Object()
        out.data = bytearray(self.data) \
            if isinstance(self.data, bytearray) else self.data
        out.xattrs = dict(self.xattrs)
        out.omap = dict(self.omap)
        return out

    def _private(self) -> bytearray:
        if not isinstance(self.data, bytearray):
            self.data = bytearray(self.data)
        return self.data

    def write(self, offset: int, data) -> bool:
        """True when `data` was kept as it is, no byte moved."""
        self.mtime = time.time()
        if offset == 0 and not self.data:
            kept = _adoptable(data)
            self.data = data if kept else bytes(data)
            return kept
        buf = self._private()
        if offset > len(buf):
            buf.extend(bytes(offset - len(buf)))
        buf[offset:offset + len(data)] = data
        return False

    def truncate(self, size: int) -> None:
        have = len(self.data)
        if size == 0:
            self.data = b""
        elif size < have:
            del self._private()[size:]
        elif size > have:
            self._private().extend(bytes(size - have))

    def window(self, offset: int, end: int | None):
        """[offset:end) as something read-only that the store never
        writes again: a view of an immutable buffer, a copy of a
        private one (whose view must never leave the store)."""
        if isinstance(self.data, bytearray):
            with memoryview(self.data) as view:
                return bytes(view[offset:end])
        # the buffer under it is immutable and the store's for good:
        # nobody resizes, pools or recycles it, and an overwrite gives
        # the object another buffer, so the window stays true
        # radoslint: disable-next=view-escape
        return memoryview(self.data)[offset:end]


class _Overlay:
    """The store's name space as one transaction has changed it so far:
    what `_validate` reads in place of a copy of every collection. Per
    collection the transaction names: the store's own dict (None when
    the transaction made the collection), the names it added that the
    dict lacks, and the dict's names it removed."""

    def __init__(self, colls: dict):
        self._base = colls
        self._colls: dict[CollectionId, tuple | None] = {}

    def _find(self, cid):
        if cid not in self._colls:
            base = self._base.get(cid)
            self._colls[cid] = None if base is None \
                else (base, set(), set())
        return self._colls[cid]

    def coll(self, cid) -> tuple:
        ent = self._find(cid)
        if ent is None:
            raise StoreError("ENOENT", f"no collection {cid}")
        return ent

    def mkcoll(self, cid) -> None:
        if self._find(cid) is not None:
            raise StoreError("EEXIST", f"collection {cid} exists")
        self._colls[cid] = (None, set(), set())

    def rmcoll(self, cid) -> None:
        base, added, removed = self.coll(cid)
        if added or (base is not None and len(removed) < len(base)):
            raise StoreError("ENOTEMPTY", f"collection {cid} not empty")
        self._colls[cid] = None

    def add(self, cid, oid) -> None:
        base, added, removed = self.coll(cid)
        removed.discard(oid)
        if base is None or oid not in base:
            added.add(oid)

    def remove(self, cid, oid) -> None:
        base, added, removed = self.need(cid, oid)
        added.discard(oid)
        if base is not None and oid in base:
            removed.add(oid)

    def need(self, cid, oid) -> tuple:
        ent = base, added, removed = self.coll(cid)
        if oid not in added and (base is None or oid not in base
                                 or oid in removed):
            raise StoreError("ENOENT", f"no object {oid} in {cid}")
        return ent


class MemStore(ObjectStore):
    def __init__(self, name: str = "memstore"):
        self.name = name
        self._colls: dict[CollectionId, dict[Ghobject, _Object]] = {}
        self._lock = threading.RLock()
        self._mounted = False
        self._used_cache: tuple[float, int] | None = None
        self.perf = PerfCounters(f"memstore:{name}")
        self.perf.add("ops")
        self.perf.add("txns")
        self.perf.add("bytes_written")

    # -- lifecycle -----------------------------------------------------------

    def mkfs(self) -> None:
        with self._lock:
            self._colls.clear()

    def mount(self) -> None:
        self._mounted = True

    def umount(self) -> None:
        self._mounted = False

    #: statfs calls land once per mgr report period; a full O(objects)
    #: rescan under the store lock each time would stall commits on a
    #: bench-scale store, so the answer is cached briefly — NEARFULL
    #: thresholds tolerate seconds of staleness
    USED_BYTES_TTL = 2.0

    def used_bytes(self) -> int:
        now = time.monotonic()
        cached = self._used_cache
        if cached is not None and now - cached[0] < self.USED_BYTES_TTL:
            return cached[1]
        with self._lock:
            used = sum(len(obj.data)
                       for coll in self._colls.values()
                       for obj in coll.values())
        self._used_cache = (now, used)
        return used

    # -- lookup helpers ------------------------------------------------------

    def _coll(self, cid: CollectionId) -> dict[Ghobject, _Object]:
        coll = self._colls.get(cid)
        if coll is None:
            raise StoreError("ENOENT", f"no collection {cid}")
        return coll

    def _obj(self, cid: CollectionId, oid: Ghobject) -> _Object:
        obj = self._coll(cid).get(oid)
        if obj is None:
            raise StoreError("ENOENT", f"no object {oid} in {cid}")
        return obj

    def _obj_create(self, cid: CollectionId, oid: Ghobject) -> _Object:
        coll = self._coll(cid)
        obj = coll.get(oid)
        if obj is None:
            obj = coll[oid] = _Object()
        return obj

    # -- transactions --------------------------------------------------------

    def _validate(self, txn: Transaction) -> None:
        """Reject impossible transactions before touching state, so apply
        below cannot fail halfway (atomicity)."""
        names = _Overlay(self._colls)
        for op in txn.ops:
            kind = op[0]
            if kind == Op.MKCOLL:
                names.mkcoll(op[1])
            elif kind == Op.RMCOLL:
                names.rmcoll(op[1])
            elif kind in (Op.TOUCH, Op.WRITE, Op.ZERO, Op.TRUNCATE,
                          Op.SETATTRS, Op.OMAP_SETKEYS, Op.OMAP_RMKEYS,
                          Op.OMAP_CLEAR):
                names.add(op[1], op[2])
            elif kind == Op.REMOVE:
                names.remove(op[1], op[2])
            elif kind == Op.RMATTR:
                names.need(op[1], op[2])
            elif kind in (Op.CLONE, Op.CLONE_RANGE):
                names.need(op[1], op[2])
                names.add(op[1], op[3])
            elif kind == Op.COLL_MOVE_RENAME:
                names.need(op[1], op[2])
                names.coll(op[3])
                names.remove(op[1], op[2])
                names.add(op[3], op[4])

    def queue_transaction(self, txn: Transaction) -> None:
        with self._lock:
            self._validate(txn)
            for op in txn.ops:
                self._apply(op)
            self.perf.inc("ops", len(txn.ops))
            self.perf.inc("txns")
        for fn in txn.on_applied:
            fn()
        for fn in txn.on_commit:
            fn()

    def _apply(self, op: tuple) -> None:
        kind = op[0]
        if kind == Op.MKCOLL:
            self._colls[op[1]] = {}
        elif kind == Op.RMCOLL:
            del self._colls[op[1]]
        elif kind == Op.TOUCH:
            self._obj_create(op[1], op[2])
        elif kind == Op.WRITE:
            _, cid, oid, offset, data, _csums = op
            t0 = time.perf_counter()
            if self._obj_create(cid, oid).write(offset, data):
                copytrack.referenced("store_write", len(data))
            else:
                copytrack.copied("store_write", len(data),
                                 time.perf_counter() - t0)
            self.perf.inc("bytes_written", len(data))
        elif kind == Op.ZERO:
            _, cid, oid, offset, length = op
            self._obj_create(cid, oid).write(offset, bytes(length))
        elif kind == Op.TRUNCATE:
            self._obj_create(op[1], op[2]).truncate(op[3])
        elif kind == Op.REMOVE:
            del self._coll(op[1])[op[2]]
        elif kind == Op.SETATTRS:
            self._obj_create(op[1], op[2]).xattrs.update(op[3])
        elif kind == Op.RMATTR:
            self._obj(op[1], op[2]).xattrs.pop(op[3], None)
        elif kind == Op.CLONE:
            _, cid, src, dst = op
            self._coll(cid)[dst] = self._obj(cid, src).clone()
        elif kind == Op.CLONE_RANGE:
            _, cid, src, dst, src_off, length, dst_off = op
            data = self._obj(cid, src).window(src_off, src_off + length)
            self._obj_create(cid, dst).write(dst_off, data)
        elif kind == Op.OMAP_SETKEYS:
            self._obj_create(op[1], op[2]).omap.update(op[3])
        elif kind == Op.OMAP_RMKEYS:
            omap = self._obj(op[1], op[2]).omap
            for key in op[3]:
                omap.pop(key, None)
        elif kind == Op.OMAP_CLEAR:
            self._obj(op[1], op[2]).omap.clear()
        elif kind == Op.COLL_MOVE_RENAME:
            _, old_cid, old_oid, new_cid, new_oid = op
            obj = self._coll(old_cid).pop(old_oid)
            self._coll(new_cid)[new_oid] = obj
        else:
            raise StoreError("EINVAL", f"unknown op {kind}")

    # -- reads ---------------------------------------------------------------

    def list_collections(self) -> list[CollectionId]:
        with self._lock:
            return sorted(self._colls)

    def collection_exists(self, cid: CollectionId) -> bool:
        with self._lock:
            return cid in self._colls

    def collection_list(self, cid: CollectionId, start: Ghobject | None = None,
                        max_count: int = 2 ** 31) -> list[Ghobject]:
        with self._lock:
            objs = sorted(self._coll(cid))
        if start is not None:
            objs = [o for o in objs if o > start]
        return objs[:max_count]

    def exists(self, cid: CollectionId, oid: Ghobject) -> bool:
        with self._lock:
            coll = self._colls.get(cid)
            return coll is not None and oid in coll

    def stat(self, cid: CollectionId, oid: Ghobject) -> dict:
        with self._lock:
            obj = self._obj(cid, oid)
            return {"size": len(obj.data), "mtime": obj.mtime,
                    "num_xattrs": len(obj.xattrs),
                    "num_omap": len(obj.omap)}

    def read(self, cid: CollectionId, oid: Ghobject, offset: int = 0,
             length: int | None = None) -> bytes | memoryview:
        end = None if length is None else offset + length
        t0 = time.perf_counter()
        with self._lock:
            data = self._obj(cid, oid).window(offset, end)
        if isinstance(data, memoryview):
            copytrack.referenced("store_read", len(data))
        else:
            copytrack.copied("store_read", len(data),
                             time.perf_counter() - t0)
        return data

    def getattr(self, cid: CollectionId, oid: Ghobject, name: str) -> bytes:
        with self._lock:
            xattrs = self._obj(cid, oid).xattrs
            if name not in xattrs:
                raise StoreError("ENODATA", f"no xattr {name} on {oid}")
            return xattrs[name]

    def getattrs(self, cid: CollectionId, oid: Ghobject) -> dict[str, bytes]:
        with self._lock:
            return dict(self._obj(cid, oid).xattrs)

    def omap_get(self, cid: CollectionId, oid: Ghobject) -> dict[str, bytes]:
        with self._lock:
            return dict(self._obj(cid, oid).omap)

    def omap_get_values(self, cid: CollectionId, oid: Ghobject,
                        keys: Iterable[str]) -> dict[str, bytes]:
        with self._lock:
            omap = self._obj(cid, oid).omap
            return {k: omap[k] for k in keys if k in omap}
