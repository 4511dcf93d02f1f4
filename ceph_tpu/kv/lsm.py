"""LSMStore: persistent log-structured KeyValueDB (the RocksDBStore role).

Re-creation of the reference's RocksDBStore essentials
(src/kv/RocksDBStore.cc over the vendored src/rocksdb/) as a compact
log-structured merge engine:

  * every batch is appended to a crc-framed WAL and fsync'd before it
    is acknowledged (rocksdb WriteBatch + WAL semantics). A record is
    binary: a value costs its own length on the log and a few bytes of
    framing, whatever its bytes are (BlueStore's deferred writes ride
    here: a shard's 8 KiB are 8 KiB of log);
  * the memtable absorbs writes; when it exceeds the flush threshold it
    is written out as an immutable sorted-run file (SSTable role) and
    the WAL is truncated. A key that was set AND deleted inside one
    memtable's life, with no older run holding it, leaves nothing: no
    value and no tombstone reaches a run (a deferred write's record
    lives a fraction of a second);
  * lookups go memtable -> runs newest-to-oldest; deletes are
    tombstones that shadow older runs;
  * when the run count exceeds the compaction trigger, runs are merged
    into one and tombstones are dropped (full compaction — the
    reference's leveled compaction collapsed to one level);
  * the MANIFEST (tmp+rename+fsync) names the live runs, so a crash
    mid-flush/mid-compaction falls back to the previous run set plus
    WAL replay.

Threads: one writer, any readers. `submit_transaction` appends, syncs
and applies, and never flushes or compacts: the owner calls
`maintain()` on the same thread between submits, where nobody waits on
it (BlueStore's commit thread, once a group's acknowledgements have
left). `get` and `iterate` may run on another thread meanwhile. `_lock`
is held only while tables change hands or are iterated, never across
file I/O. A flush and a compaction each leave a
span (`kv_flush`, `kv_compact`) from the thread that ran them.

Idiomatic divergences: runs are loaded into memory at open (block
cache = whole-file residency — state here is control-plane-sized);
records and runs are length-framed, not varint-framed blocks.
"""
from __future__ import annotations

import json
import os
import struct
import threading
import time

from ceph_tpu.kv.keyvaluedb import KeyValueDB, KVTransaction
from ceph_tpu.utils import tracer
from ceph_tpu.utils.crash import SimulatedCrash  # noqa: F401 (re-export)

_TOMB = None          # tombstone marker inside tables
_MISSING = object()   # no entry in a table, where None is a tombstone
_WAL_V = b"\x02"      # a log record's first byte: binary ops follow
_RUN_MAGIC = b"RUN2"  # a sorted run's first bytes, after its crc
#: one op of a log record: kind, and the lengths of its prefix, key and
#: value (-1: it has none); the three follow
_OP = struct.Struct("<BHIi")
_KINDS = ("set", "rm", "rmprefix")
#: one entry of a sorted run: the lengths of its key and of its value
#: (-1: a tombstone); the two follow
_ENTRY = struct.Struct("<Ii")


def _crc32c(data: bytes) -> int:
    from ceph_tpu.native import ec_native
    return ec_native.crc32c(data)


def _fsync_dir(path: str) -> None:
    fd = os.open(path, os.O_RDONLY)
    try:
        os.fsync(fd)
    finally:
        os.close(fd)


def _encode_ops(ops: list[tuple]) -> bytes:
    """A batch as one log record's body."""
    parts = [_WAL_V]
    for op in ops:
        prefix = op[1].encode()
        key = op[2].encode() if len(op) > 2 else b""
        value = op[3] if len(op) > 3 else None
        parts += (_OP.pack(_KINDS.index(op[0]), len(prefix), len(key),
                           -1 if value is None else len(value)),
                  prefix, key, value or b"")
    return b"".join(parts)


def _decode_ops(rec: bytes):
    """The ops of a log record's body, as `KVTransaction.ops` has them."""
    if rec[:1] != _WAL_V:
        raise IOError("wal.log: a record of another format (written by "
                      "an older program?)")
    at = 1
    while at < len(rec):
        kind, np, nk, nv = _OP.unpack_from(rec, at)
        at += _OP.size
        prefix = rec[at:at + np].decode()
        key = rec[at + np:at + np + nk].decode()
        at += np + nk
        if kind == 0:
            yield ("set", prefix, key, rec[at:at + nv])
            at += nv
        else:
            yield (_KINDS[kind], prefix, key)


class LSMStore(KeyValueDB):

    FLUSH_BYTES = 4 * 1024 * 1024     # memtable flush threshold
    #: the log's own threshold, in memtables: values that were set and
    #: deleted again (deferred writes) fill the log and not the
    #: memtable, and a mount replays the log whole (rocksdb's
    #: max_total_wal_size role)
    WAL_FLUSHES = 4
    COMPACT_RUNS = 6                  # full-compaction trigger

    def __init__(self, path: str, flush_bytes: int | None = None):
        self.path = path
        if flush_bytes is not None:
            self.FLUSH_BYTES = flush_bytes
        # "prefix\x00key" -> bytes | None(tombstone)
        self._memtable: dict[str, bytes | None] = {}
        self._mem_bytes = 0
        #: keys of the memtable that no run holds: deleted, such a key
        #: leaves no tombstone behind
        self._born: set[str] = set()
        self._born_dropped = 0          # of them, deleted since the flush
        self._runs: list[dict[str, bytes | None]] = []   # newest first
        self._run_files: list[str] = []
        self._wal = None
        self._wal_bytes = 0
        self._next_file = 1
        self.fail_after_wal = False     # SimulatedCrash hook
        self._lock = threading.Lock()
        #: since the store was made: `fsync`s (log, runs, manifest,
        #: directory), bytes written to the log and to runs, flushes of
        #: the memtable, compactions
        self.stats = dict.fromkeys(
            ("fsyncs", "bytes_written", "memtable_flushes", "compactions"),
            0)

    # -- lifecycle -----------------------------------------------------------

    def open(self) -> None:
        os.makedirs(os.path.join(self.path, "sst"), exist_ok=True)
        manifest = os.path.join(self.path, "MANIFEST")
        if os.path.exists(manifest):
            with open(manifest) as f:
                m = json.load(f)
            self._run_files = list(m["runs"])
            self._next_file = m["next"]
            self._runs = [self._load_run(fn) for fn in self._run_files]
        self._replay_wal()
        self._wal = open(os.path.join(self.path, "wal.log"), "ab")
        self._wal_bytes = self._wal.tell()

    def close(self) -> None:
        if self._wal is not None:
            self._wal.close()
            self._wal = None

    # -- WAL -----------------------------------------------------------------

    def _wal_path(self) -> str:
        return os.path.join(self.path, "wal.log")

    def _replay_wal(self) -> None:
        path = self._wal_path()
        if not os.path.exists(path):
            return
        with open(path, "rb") as f:
            blob = f.read()
        off = 0
        while off + 8 <= len(blob):
            length, crc = struct.unpack_from("<II", blob, off)
            rec = blob[off + 8:off + 8 + length]
            if len(rec) < length or _crc32c(rec) != crc:
                break                       # torn tail: stop replay here
            self._apply(_decode_ops(rec))
            off += 8 + length

    # -- batch submit --------------------------------------------------------

    def submit_transaction(self, txn: KVTransaction,
                           sync: bool = True) -> None:
        if not txn.ops:
            return
        rec = _encode_ops(txn.ops)
        self._wal.write(struct.pack("<II", len(rec), _crc32c(rec)) + rec)
        self._wal.flush()
        self.stats["bytes_written"] += 8 + len(rec)
        self._wal_bytes += 8 + len(rec)
        if sync:
            os.fsync(self._wal.fileno())
            self.stats["fsyncs"] += 1
        if self.fail_after_wal:
            raise SimulatedCrash("crash between WAL append and apply")
        with self._lock:
            self._apply(txn.ops)

    def maintain(self) -> None:
        """Flush the memtable if it, or the log, is over its threshold,
        and compact if that made one run too many. On the writer's
        thread."""
        if self._mem_bytes >= self.FLUSH_BYTES or \
                self._wal_bytes >= self.WAL_FLUSHES * self.FLUSH_BYTES:
            self._flush()

    def _apply(self, ops) -> None:
        for op in ops:
            if op[0] == "set":
                self._mem_set(f"{op[1]}\x00{op[2]}", op[3])
            elif op[0] == "rm":
                self._mem_set(f"{op[1]}\x00{op[2]}", _TOMB)
            elif op[0] == "rmprefix":
                self._rm_prefix_mem(op[1])

    def _mem_set(self, fq: str, value: bytes | None) -> None:
        mem = self._memtable
        old = mem.get(fq, _MISSING)
        if old is _MISSING:
            held = any(fq in run for run in self._runs)
            if value is _TOMB and not held:
                return                  # nothing anywhere to shadow
            if not held:
                self._born.add(fq)
            old = None
            self._mem_bytes += len(fq)
        elif value is _TOMB and fq in self._born:
            # set and deleted inside this memtable's life: no run ever
            # sees the value, and there is nothing older to shadow
            del mem[fq]
            self._born.discard(fq)
            self._born_dropped += 1
            self._mem_bytes -= len(fq) + len(old)
            return
        mem[fq] = value
        self._mem_bytes += (len(value) if value else 0) \
            - (len(old) if old else 0)

    def _rm_prefix_mem(self, prefix: str) -> None:
        """Tombstone every key under `prefix` visible anywhere."""
        p = prefix + "\x00"
        names = {k for k in self._memtable if k.startswith(p)}
        for run in self._runs:
            names.update(k for k in run if k.startswith(p))
        for k in names:
            self._mem_set(k, _TOMB)

    # -- flush / compaction --------------------------------------------------

    def _run_path(self, name: str) -> str:
        return os.path.join(self.path, "sst", name)

    def _load_run(self, name: str) -> dict[str, bytes | None]:
        with open(self._run_path(name), "rb") as f:
            blob = f.read()
        crc, = struct.unpack_from("<I", blob, 0)
        body = blob[4:]
        if _crc32c(body) != crc:
            raise IOError(f"sst {name}: crc mismatch")
        if body[:4] != _RUN_MAGIC:
            raise IOError(f"sst {name}: another format (written by an "
                          f"older program?)")
        table: dict[str, bytes | None] = {}
        at, end, size = 4, len(body), _ENTRY.size
        while at < end:
            nk, nv = _ENTRY.unpack_from(body, at)
            at += size
            key = body[at:at + nk].decode()
            at += nk
            if nv < 0:
                table[key] = _TOMB
            else:
                table[key] = body[at:at + nv]
                at += nv
        return table

    def _write_run(self, table: dict[str, bytes | None]) -> tuple[str, int]:
        """`table` as a new run file, sorted. -> (its name, its bytes)"""
        name = f"{self._next_file:06d}.sst"
        self._next_file += 1
        parts = [_RUN_MAGIC]
        for k, v in sorted(table.items()):
            key = k.encode()
            parts += (_ENTRY.pack(len(key), -1 if v is None else len(v)),
                      key, v or b"")
        body = b"".join(parts)
        tmp = self._run_path(name) + ".tmp"
        with open(tmp, "wb") as f:
            f.write(struct.pack("<I", _crc32c(body)))
            f.write(body)
            f.flush()
            os.fsync(f.fileno())
        self.stats["bytes_written"] += 4 + len(body)
        self.stats["fsyncs"] += 1
        os.replace(tmp, self._run_path(name))
        return name, 4 + len(body)

    def _commit_manifest(self) -> None:
        tmp = os.path.join(self.path, "MANIFEST.tmp")
        with open(tmp, "w") as f:
            json.dump({"runs": self._run_files, "next": self._next_file},
                      f)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, os.path.join(self.path, "MANIFEST"))
        _fsync_dir(self.path)
        self.stats["fsyncs"] += 2

    def _flush(self) -> None:
        if not self._memtable and not self._wal_bytes:
            return
        t0 = time.perf_counter()
        entries, mem_bytes, nbytes = len(self._memtable), self._mem_bytes, 0
        if self._memtable:
            # the writer alone changes the memtable, and this is it
            name, nbytes = self._write_run(self._memtable)
            self._run_files.insert(0, name)
            with self._lock:
                self._runs.insert(0, dict(self._memtable))
            self._commit_manifest()
            with self._lock:        # the run above holds every key of it
                self._memtable.clear()
        dropped, self._born_dropped = self._born_dropped, 0
        self._born.clear()
        self._mem_bytes = 0
        self.stats["memtable_flushes"] += 1
        # what the log held is durable in the run, or was deleted again:
        # start a fresh log
        self._wal.close()
        os.truncate(self._wal_path(), 0)
        self._wal = open(self._wal_path(), "ab")
        self._wal_bytes = 0
        self._span("kv_flush", t0, mem_bytes, nbytes, entries, dropped)
        if len(self._run_files) > self.COMPACT_RUNS:
            self._compact()

    def _compact(self) -> None:
        """Merge every run into one; tombstones drop out (nothing older
        remains to shadow)."""
        t0 = time.perf_counter()
        merged: dict[str, bytes | None] = {}
        for run in reversed(self._runs):         # oldest first
            merged.update(run)
        merged = {k: v for k, v in merged.items() if v is not None}
        entries_in = sum(len(run) for run in self._runs)
        bytes_in = sum(os.path.getsize(self._run_path(fn))
                       for fn in self._run_files)
        name, nbytes = self._write_run(merged)
        old_files = self._run_files
        self._run_files = [name]
        with self._lock:
            self._runs = [merged]
        self._commit_manifest()
        self.stats["compactions"] += 1
        for fn in old_files:
            try:
                os.unlink(self._run_path(fn))
            except OSError:
                pass
        self._span("kv_compact", t0, bytes_in, nbytes, len(merged),
                   entries_in - len(merged))

    def _span(self, name: str, t0: float, bytes_in: int, bytes_out: int,
              entries: int, dropped: int) -> None:
        """`kv_flush`: the memtable's bytes in, the run's out, the
        entries written, and the keys that were set and deleted since
        the last flush and so were not. `kv_compact`: the old runs'
        bytes in, the one run's out, its entries, and the shadowed
        values and tombstones left behind."""
        if tracer.active():
            tracer.record_span(
                name, t0, (time.perf_counter() - t0) * 1e6,
                {"bytes_in": bytes_in, "bytes_out": bytes_out,
                 "entries": entries, "dropped": dropped},
                getattr(self, "name", type(self).__name__))

    def compact(self) -> None:
        """Explicit full compaction (rocksdb CompactRange)."""
        self._flush()
        if len(self._run_files) > 1:
            self._compact()

    # -- reads ---------------------------------------------------------------

    def get(self, prefix: str, key: str) -> bytes | None:
        # no lock: one lookup a table, and a flush puts the memtable's
        # keys into a run before it clears them
        fq = f"{prefix}\x00{key}"
        value = self._memtable.get(fq, _MISSING)
        if value is not _MISSING:
            return value
        for run in self._runs:
            value = run.get(fq, _MISSING)
            if value is not _MISSING:
                return value
        return None

    def iterate(self, prefix: str, start: str = ""):
        p = prefix + "\x00"
        view: dict[str, bytes | None] = {}
        with self._lock:
            for run in reversed(self._runs):
                for k, v in run.items():
                    if k.startswith(p):
                        view[k] = v
            for k, v in self._memtable.items():
                if k.startswith(p):
                    view[k] = v
        for k in sorted(view):
            key = k[len(p):]
            if view[k] is not None and key >= start:
                yield key, view[k]
