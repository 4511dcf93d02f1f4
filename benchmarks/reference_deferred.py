"""The plain reference for a store with a DEFERRED write path (the
configuration `radosbench_ec83_tpu_64k_on_bluestore`): what such a store
owes between an acknowledgement and a kill, where each acknowledged
byte must then be found, and the least a small `write_full` on an
erasure pool must put on the stores' devices.

It imports nothing of the program and starts no thread. The contract of
a store that commits later than it queues is `reference_bluestore`'s
(readable when queued, durable when acknowledged, a collection's
transactions a prefix); this file adds the path a write SHORTER THAN
`line` takes (upstream: BlueStore.cc `_do_alloc_write`,
`bluestore_prefer_deferred_size`; the rule is strict, a write of exactly
`line` is not deferred). Its stages, in order:

    queued         prepared and readable; nothing of it is durable
    kv_synced      its bytes AND its metadata are in the KV's synced
                   log, as one record; ACKNOWLEDGED from here on
    written        its bytes are written to their allocation units,
                   unsynced: a kill may or may not have kept them
    block_synced   the block device holds them, synced; the record is
                   still in the KV
    record_removed a later synced KV batch has removed the record

A kill between any two leaves a store that a mount REPLAYS: every
record found is written to its units, synced, and removed; after it an
acknowledged write is on its units and no record is left, and a second
replay of the same records changes nothing. A write of `line` or more
goes the other way round: units written and synced FIRST, then the KV,
acknowledged from that sync; it has no record at any time.

The shards at rest stay `benchmarks.reference.expected_shards`'s to say.
"""
from __future__ import annotations

from benchmarks import reference_bluestore

STAGES = ("queued", "kv_synced", "written", "block_synced",
          "record_removed")


def is_deferred(nbytes: int, line: int) -> bool:
    """An empty write stores nothing; one of `line` or more is written
    to the device before its metadata commits."""
    return 0 < nbytes < line


def units(nbytes: int, au: int) -> int:
    return -(-nbytes // au)


def at_kill(stage: str) -> dict:
    """Where a deferred write stands after a kill at `stage`, before
    any replay: `acknowledged`; `record`, whether the KV holds its
    record; `on_units`, whether its units hold its bytes. None is
    "either": the kill decides."""
    i = STAGES.index(stage)
    return {"acknowledged": i >= 1,
            "record": 1 <= i <= 3,
            "on_units": None if i == 2 else i >= 3}


def after_replay(stage: str) -> dict:
    """The same after the mount that follows: an acknowledged write is
    on its units, and no record is left, whatever the stage."""
    acked = at_kill(stage)["acknowledged"]
    return {"acknowledged": acked, "record": False,
            "on_units": True if acked else None}


class Device:
    """A store's two media as a kill finds them: the KV (objects'
    metadata and the records, durable once `sync_kv` has run) and the
    block device (unit -> bytes, durable once `sync_block` has run).
    What was written and not synced is kept apart: a kill drops any of
    it, or none."""

    def __init__(self, au: int, line: int):
        self.au, self.line = au, line
        self.kv: dict = {"objects": {}, "records": {}}
        self.block: dict[int, bytes] = {}
        self._kv_pending: list = []
        self._block_pending: dict[int, bytes] = {}
        self._next_unit = 0
        self._seq = 0

    # -- the write path, one stage a call ------------------------------------

    def queue(self, name, data: bytes) -> dict:
        """Stage `queued`: allocate, pad, and stage the metadata (and,
        under the line, the record). -> the write, for the next calls."""
        n = units(len(data), self.au)
        padded = data.ljust(n * self.au, b"\x00")
        first, self._next_unit = self._next_unit, self._next_unit + n
        w = {"name": name, "size": len(data), "units": list(range(
            first, first + n)), "padded": padded, "seq": None,
            "deferred": is_deferred(len(data), self.line)}
        if w["deferred"]:
            self._seq += 1
            w["seq"] = self._seq
        return w

    def _chunks(self, w: dict):
        for i, unit in enumerate(w["units"]):
            yield unit, w["padded"][i * self.au:(i + 1) * self.au]

    def write_units(self, w: dict) -> None:
        self._block_pending.update(self._chunks(w))

    def sync_block(self) -> None:
        self.block.update(self._block_pending)
        self._block_pending = {}

    def log(self, w: dict) -> None:
        """The write's KV batch, unsynced: its object's metadata, and
        its record if it is deferred."""
        self._kv_pending.append(("object", w["name"], {
            "size": w["size"], "units": w["units"]}))
        if w["deferred"]:
            self._kv_pending.append(("record", w["seq"],
                                     dict(self._chunks(w))))

    def unlog(self, w: dict) -> None:
        self._kv_pending.append(("record", w["seq"], None))

    def sync_kv(self) -> None:
        for kind, key, value in self._kv_pending:
            table = self.kv["objects" if kind == "object" else "records"]
            if value is None:
                table.pop(key, None)
            else:
                table[key] = value
        self._kv_pending = []

    def write_full(self, name, data: bytes, upto: str = STAGES[-1]) -> dict:
        """One whole-object write, taken as far as stage `upto` (a
        write of `line` or more has no stages: it runs whole, or, at
        `queued`, not at all). -> the write."""
        w = self.queue(name, data)
        stop = STAGES.index(upto)
        if not w["deferred"]:
            if stop >= 1:
                self.write_units(w)
                self.sync_block()
                self.log(w)
                self.sync_kv()
            return w
        steps = (lambda: (self.log(w), self.sync_kv()),
                 lambda: self.write_units(w),
                 self.sync_block,
                 lambda: (self.unlog(w), self.sync_kv()))
        for step in steps[:stop]:
            step()
        return w

    # -- a kill, and the mount after it -------------------------------------

    def kill(self, keep_unsynced: bool) -> "Device":
        """What a fresh mount finds: the synced state, with all of the
        unsynced block writes or none of them (an unsynced KV batch is
        torn off the log either way)."""
        out = Device(self.au, self.line)
        out.kv = {"objects": dict(self.kv["objects"]),
                  "records": dict(self.kv["records"])}
        out.block = dict(self.block)
        if keep_unsynced:
            out.block.update(self._block_pending)
        return out

    def replay(self) -> int:
        """A mount's replay: every record to its units, in the order
        queued, synced, then removed. -> how many it found."""
        found = sorted(self.kv["records"])
        for seq in found:
            self._block_pending.update(self.kv["records"][seq])
        self.sync_block()
        for seq in found:
            self._kv_pending.append(("record", seq, None))
        self.sync_kv()
        return len(found)

    def read(self, name) -> bytes | None:
        """The object's bytes from its units; None if the KV has no such
        object, and KeyError if a unit was never written."""
        on = self.kv["objects"].get(name)
        if on is None:
            return None
        return b"".join(self.block[u] for u in on["units"])[:on["size"]]


# -- which states a mount may show ----------------------------------------------

def states_after_kill(txns: list[list[tuple]], at: int, stage: str,
                      c) -> list[dict | None]:
    """The states of collection `c` that a fresh mount may show after a
    kill with every transaction before `at` acknowledged and the `at`-th
    at `stage`: `reference_bluestore.states_after_kill` with the
    acknowledged set this path gives."""
    acked = set(range(at + 1 if at_kill(stage)["acknowledged"] else at))
    return reference_bluestore.states_after_kill(txns, acked, c)


# -- the least a write puts on the devices --------------------------------------

def least_device_bytes(nbytes: int, k: int, m: int, chunk: int, au: int,
                       line: int) -> dict:
    """What one `write_full` of `nbytes` on a k+m pool must put on the
    k+m stores' devices, object metadata apart: each shard padded to
    `au` on a block device; under the line the same bytes once more on
    the KV's log; and the syncs that must come before the write is
    acknowledged, a store: one of the KV under the line, the block
    device's and then the KV's at the line and over it."""
    stripes = max(1, -(-nbytes // (k * chunk)))
    shard = stripes * chunk
    padded = units(shard, au) * au
    deferred = is_deferred(shard, line)
    block = (k + m) * padded
    kv = block if deferred else 0
    return {"shard_bytes": shard, "deferred": deferred,
            "block_bytes": block, "kv_bytes": kv,
            "bytes_per_user_byte": (block + kv) / nbytes,
            "kv_bytes_per_user_byte": kv / nbytes,
            "syncs_before_ack": ("kv",) if deferred else ("block", "kv")}
