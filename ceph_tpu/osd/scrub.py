"""PG scrub: background verification + repair of replica/shard state.

Re-creation of the reference scrub machinery (src/osd/scrubber/
pg_scrubber.h:177 state machine, scrub_backend.h:101 per-shard map
compare, ECBackend.cc:1092-1120 deep shard verify):

  * the primary asks every acting peer for a SCRUB MAP — per object:
    size, attrs digest, and (deep) content digests; it builds its own
    map the same way;
  * client writes are gated out for the duration of a scrub round (the
    reference's scrub range write blocking) so repairs never race an
    acknowledged write;
  * maps are compared per object: corrupt shards are self-certified by
    the stored per-chunk crc on EC pools (or the store's blob crc on
    FileStore); replicated copies vote — ABSENCE VOTES TOO, so a stale
    holder cannot resurrect a deleted object — and only a strict
    majority is repaired toward (no majority = inconsistency reported,
    never guessed, matching the reference's refusal to auto-repair
    ambiguous objects);
  * repairs ride the existing recovery machinery: EC shards are
    reconstructed from k survivors and pushed; replicated copies
    converge on the majority fingerprint, pulled first if the primary
    itself is wrong.

Observability (the continuous-integrity layer):

  * deep-scrub content digests are BATCHED through the offload
    service's CrcJob path (`OffloadService.crc32c_blocks`) — one
    coalesced hash job per scan chunk instead of a per-chunk host loop,
    bit-identical to the `ec_native.crc32c` host fallback because both
    run the same slice-by-8 kernel with the same seed;
  * scans are CHUNKED (`osd_scrub_chunk_max` objects per grant, an
    optional `osd_scrub_sleep` pause between chunks) and each chunk
    pre-pays a zero-work grant token through the op queue under the
    declared background `scrub` class, so dmclock arbitration paces
    scrub against client I/O while its reservation guarantees forward
    progress;
  * every round updates per-PG progress (`pg.scrub_progress`), stamps
    (`last_scrub_stamp` / `last_deep_scrub_stamp`), cumulative
    `pg.scrub_stats`, and the per-PG inconsistent-object registry
    (`pg.inconsistent_objects`, the `list-inconsistent-obj` source);
    mismatches/repairs/aborts drop flight-recorder crumbs and the
    process-wide "scrub" perf logger rides the mgr report leg.

Scheduling and reservations (the reference's OSD::sched_scrub and its
scrub reserver):

  * each OSD keeps the PGs it is primary of in the order they are due
    (`ScrubQueue`: last round + `osd_scrub_interval`, an operator's
    request first) and its `_scrub_loop` starts ONE round at a time;
  * a round holds one `osd_max_scrubs` slot on every up member of its
    acting set (`_reserve_acting_set`, MOSDScrubReserve): the lowest
    id's first, then the others' at once, its own daemon's at its
    place. Nobody waits for a slot: a daemon whose slots are taken
    rejects at once, the primary gives back what it holds, the PG is
    put back `retry_delay` later and the loop goes on to the next one.
    A peer that does not answer costs one wait of
    `osd_scrub_reserve_timeout`. A member gives its slot back when it
    has sent its map of the round's last range (the request says so:
    no message of its own), the primary when the round has ended;
  * rounds follow one another without a pause, and the turn goes round
    the acting set: a daemon whose slot comes back holds its own next
    round back by SCRUB_TURN_S for every place it stands behind the
    primary of the round that ended (`turn_hold`), so the next in line
    asks first and the others find their slot taken before they ask;
  * a round is one `scrub_round` span (pgid, deep, state, objects,
    bytes and its legs: `reserve_us`, `grant_wait_us`, `scan_us`,
    `digest_us`, `compare_us`, and what it found: `errors`,
    `repaired`), every scan chunk on every OSD that
    builds a map one `scrub_chunk` span (objects, bytes, blocks).

Idiomatic divergences: one map exchange per range of
`osd_scrub_chunk_max` names, the range's writes gated meanwhile, where
the reference's chunky scrub also waits out per-object locks (PGs here
are small); light scrub compares size+attrs digests, deep scrub
re-reads and re-hashes everything — same split as the reference's
shallow/deep modes.
"""
from __future__ import annotations

import asyncio
import json
import threading
import time
from typing import TYPE_CHECKING

import numpy as np

from ceph_tpu.msg.messages import MOSDRepScrub, MOSDRepScrubMap
from ceph_tpu.objectstore.store import StoreError
from ceph_tpu.osd.reserver import retry_delay as _stretched
from ceph_tpu.utils import flight, tracer
from ceph_tpu.utils.dout import dout
from ceph_tpu.utils.perf_counters import (TYPE_HISTOGRAM,
                                          PerfCountersCollection)

if TYPE_CHECKING:
    from ceph_tpu.osd.pg import PGInstance

SCRUB_PEER_TIMEOUT = 10.0
#: bound on one range's wait for its QoS grant. Grants are taken with
#: the PG write gate OPEN (client writes flow while scrub waits its
#: turn), so there is no gate/queue deadlock — the bound is pure
#: robustness: the scheduler shapes scrub, it must never wedge it.
#: On timeout the range proceeds ungranted (counted + crumbed).
SCRUB_GRANT_TIMEOUT = 5.0
#: how long a PG whose reservation was rejected is put back (stretched
#: by up to as much again by `retry_delay`, so that primaries which
#: were rejected together do not come back together)
SCRUB_RETRY_S = 0.3
#: a place in the turn (`turn_hold`): about the time a reservation
#: takes to reach every member over a loaded loop, so that the daemon
#: a place further back finds its slot taken when its own hold ends
SCRUB_TURN_S = 0.1
#: reservations an operator's request (`scrub_all`) may lose in a row
#: before it is answered with the last `reserve_failed`
SCRUB_REQUEST_ATTEMPTS = 40
#: the legs of a round that its `scrub_round` span carries, as `<leg>_us`
_LEGS = ("reserve", "grant_wait", "scan", "digest", "compare")
_SCAN_YIELD_EVERY = 32      # objects hashed between event-loop yields
_DIGEST_BLOCK = 4096        # replicated-pool digest batch block size

# fingerprint sentinel: the object does not exist on that OSD. A real
# value (not exclusion) so deletions can win the majority vote.
ABSENT = "__absent__"

_perf_lock = threading.Lock()


def scrub_perf():
    """The process-wide "scrub" perf logger, created on first use.
    Rides `perf dump` and the MgrClient report leg via extra_loggers
    (exported with the `scrub_` prefix: `scrub_bytes_hashed`, ...)."""
    coll = PerfCountersCollection.instance()
    with _perf_lock:
        pc = coll.get("scrub")
        if pc is not None:
            return pc
        pc = coll.create("scrub")
        pc.add("bytes_hashed",
               description="content bytes digested by deep scrub")
        pc.add("objects_hashed",
               description="objects whose content digests were computed")
        pc.add("rounds",
               description="scrub rounds completed on this node's "
                           "primary PGs")
        pc.add("deep_rounds",
               description="deep rounds among the completed rounds")
        pc.add("chunks",
               description="scan chunks processed (each chunk = one "
                           "QoS grant under the scrub class)")
        pc.add("errors_found",
               description="inconsistent copies/shards detected by "
                           "map compare")
        pc.add("errors_repaired",
               description="copies/shards repaired through the "
                           "recovery machinery")
        pc.add("errors_unrepaired",
               description="objects left unrepaired (no majority to "
                           "repair toward)")
        pc.add("aborts",
               description="scrub rounds that died on an exception or "
                           "cancellation")
        pc.add("grant_timeouts",
               description="scan chunks that proceeded after their QoS "
                           "grant timed out (forward-progress escape "
                           "hatch)")
        pc.add("reserve_failures",
               description="scrub rounds given up because an acting-set "
                           "member had no free slot (rejected at once) or "
                           "did not answer in time")
        pc.add("digest_batch_blocks", type=TYPE_HISTOGRAM,
               description="blocks per offloaded digest batch")
        pc.add("digest_batch_us", type=TYPE_HISTOGRAM,
               description="wall microseconds per digest batch")
        return pc


class ScrubProgress:
    """Live progress of one scrub round, published at `pg.scrub_progress`
    while the round runs (mgr progress events + admin `last_scrub`)."""

    __slots__ = ("pgid", "deep", "state", "objects_total",
                 "objects_scrubbed", "bytes_hashed", "started_mono", "legs")

    def __init__(self, pgid, deep: bool):
        self.pgid = str(pgid)
        self.deep = deep
        self.state = "scrubbing"
        self.objects_total = 0
        self.objects_scrubbed = 0
        self.bytes_hashed = 0
        self.started_mono = time.monotonic()
        self.legs = dict.fromkeys(_LEGS, 0.0)   # seconds, on the primary

    def finish(self, state: str = "done") -> None:
        self.state = state

    def to_dict(self) -> dict:
        dt = max(1e-9, time.monotonic() - self.started_mono)
        return {"pgid": self.pgid, "deep": self.deep, "state": self.state,
                "objects_scrubbed": self.objects_scrubbed,
                "objects_total": self.objects_total,
                "bytes_hashed": self.bytes_hashed,
                "bytes_per_s": round(self.bytes_hashed / dt, 1),
                "elapsed_s": round(dt, 3)}


def _cfg(pg: "PGInstance", name: str, default):
    try:
        v = pg.host.config.get(name)
        return default if v is None else v
    except Exception:
        return default


async def _qos_grant(pg: "PGInstance") -> None:
    """Pre-pay one scan chunk through the op queue under the declared
    background `scrub` class: the grant is a zero-work token billed at
    one IO cost unit, so dmclock paces scrub against client load and
    the class reservation guarantees it keeps moving. Bounded wait —
    see SCRUB_GRANT_TIMEOUT."""
    q = getattr(pg.host, "op_queue", None)
    if q is None:
        return
    done = asyncio.get_running_loop().create_future()

    async def work():
        if not done.done():
            done.set_result(None)

    # distinct key: the grant must not ride (and stall behind) this
    # PG's own client-write pipeline window
    if not q.enqueue(("scrub", pg.pgid.pool, pg.pgid.ps), work,
                     klass="scrub", nbytes=q.sched.cost_per_io_bytes):
        return
    try:
        await asyncio.wait_for(done, SCRUB_GRANT_TIMEOUT)
    except asyncio.TimeoutError:
        scrub_perf().inc("grant_timeouts")
        flight.record("scrub_grant_timeout", f"pg.{pg.pgid}",
                      waited_s=SCRUB_GRANT_TIMEOUT)


def _in_range(oid: str, oid_range) -> bool:
    """Membership in a half-open name range `(lo, hi]` (None = open
    end). Exclusive lo / inclusive hi so consecutive ranges sharing a
    boundary partition the namespace with no gap and no overlap."""
    lo, hi = oid_range
    return (lo is None or oid > lo) and (hi is None or oid <= hi)


async def build_scrub_map(pg: "PGInstance", deep: bool,
                          progress: "ScrubProgress | None" = None,
                          oid_range=None, paced: bool = True) -> dict:
    """Per-object scrub entries for the local store (the reference's
    build_scrub_map_chunk / be_scan_list). With `oid_range=(lo, hi]`
    only names inside the range are scanned — the primary drives the
    round range-by-range and peers answer for exactly the requested
    slice, so absence within a range map is authoritative. Chunked:
    every `osd_scrub_chunk_max` objects cost one QoS grant when
    `paced` (standalone/full builds; range scans are paced by the
    primary at the range level and run here with paced=False), deep
    content digests for a chunk are hashed as ONE offload batch, and
    an optional `osd_scrub_sleep` pause between chunks yields the disk
    to client I/O. Yields to the event loop periodically: a large deep
    scan must not stall heartbeats."""
    if pg.pool.type == "erasure" and (oid_range is None
                                      or oid_range[0] is None):
        # once per round, on the first range
        _gc_rollback_generations(pg)
    oids = sorted(pg.list_objects())
    if oid_range is not None:
        oids = [o for o in oids if _in_range(o, oid_range)]
    elif progress is not None:
        progress.objects_total = len(oids)
    chunk_max = max(1, int(_cfg(pg, "osd_scrub_chunk_max", 32)))
    sleep_s = float(_cfg(pg, "osd_scrub_sleep", 0.0))
    out: dict[str, dict] = {}
    for start in range(0, len(oids), chunk_max):
        chunk = oids[start:start + chunk_max]
        if paced:
            await _qos_grant(pg)
        await _scan_chunk(pg, chunk, deep, out, progress)
        scrub_perf().inc("chunks")
        if progress is not None:
            progress.objects_scrubbed += len(chunk)
        if paced and sleep_s > 0 and start + chunk_max < len(oids):
            await asyncio.sleep(sleep_s)
    return out


async def _scan_chunk(pg: "PGInstance", oids: list, deep: bool,
                      out: dict, progress: "ScrubProgress | None") -> None:
    """Scan one chunk of objects: metadata host-side, deep content
    digests deferred into one `_digest_batch` offload job. One
    `scrub_chunk` span, on whichever OSD builds the map."""
    with tracer.span("scrub_chunk") as sp:
        nbytes, nblocks = await _scan_chunk_body(pg, oids, deep, out,
                                                 progress)
        if sp is not None:
            sp.tags.update(objects=len(oids), bytes=nbytes, blocks=nblocks)


async def _scan_chunk_body(pg: "PGInstance", oids: list, deep: bool,
                           out: dict, progress: "ScrubProgress | None"
                           ) -> tuple[int, int]:
    """-> (content bytes, blocks) digested for the chunk."""
    from ceph_tpu.native import ec_native
    store = pg.host.store
    cid = pg.backend.coll()
    pend: list = []         # (oid, ent, data, csum-or-None)
    for i, oid in enumerate(oids):
        if i % _SCAN_YIELD_EVERY == _SCAN_YIELD_EVERY - 1:
            await asyncio.sleep(0)
        gh = pg.backend.ghobject(oid)
        ent: dict = {"corrupt": False}
        try:
            attrs = store.getattrs(cid, gh)
            st = store.stat(cid, gh)
            ent["size"] = st["size"]
            ent["attr_digest"] = ec_native.crc32c(
                b"\x00".join(k.encode() + b"=" + v
                             for k, v in sorted(attrs.items())))
            if pg.pool.type == "erasure":
                ent["shard"] = int(attrs.get("shard", b"-1"))
                ent["version"] = list(
                    json.loads(attrs.get("version", b"[0,0]")))
                csum = json.loads(attrs.get("csum", b"[]"))
                if deep:
                    data = store.read(cid, gh)
                    c = pg.backend.sinfo.chunk_size
                    if len(data) != len(csum) * c:
                        ent["corrupt"] = True
                    else:
                        pend.append((oid, ent, data, csum))
            elif deep:
                data = store.read(cid, gh)
                omap = store.omap_get(cid, gh)
                ent["omap_digest"] = ec_native.crc32c(
                    b"\x00".join(k.encode() + b"=" + v
                                 for k, v in sorted(omap.items())))
                pend.append((oid, ent, data, None))
        except StoreError as e:
            # a FileStore blob whose crc gate refuses the read is a
            # corrupt local copy — exactly what scrub exists to find
            dout("scrub", 1, f"scrub read {oid}: {e}")
            ent["corrupt"] = True
        out[oid] = ent
    return await _digest_batch(pg, pend, progress) if pend else (0, 0)


async def _digest_batch(pg: "PGInstance", pend: list,
                        progress: "ScrubProgress | None"
                        ) -> tuple[int, int]:
    """Hash one chunk's content as a single crc32c block batch through
    the offload service (host fallback: the same `ec_native`
    slice-by-8 kernel — bit-identical either way). EC shards check the
    per-block crcs against the stored csum vector; replicated copies
    fold the block crcs into one whole-object digest."""
    from ceph_tpu.native import ec_native
    from ceph_tpu.offload.service import get_service_or_none
    perf = scrub_perf()
    t0 = time.perf_counter()
    ec = pg.pool.type == "erasure"
    block = pg.backend.sinfo.chunk_size if ec else _DIGEST_BLOCK
    batch: list[np.ndarray] = []
    counts: list[int] = []
    total_bytes = 0
    for oid, ent, data, csum in pend:
        n, tail = divmod(len(data), block)
        if tail:
            n += 1
            buf = np.zeros(n * block, dtype=np.uint8)
            buf[:len(data)] = np.frombuffer(data, dtype=np.uint8)
        else:
            buf = np.frombuffer(data, dtype=np.uint8)
        if n:
            batch.append(buf.reshape(n, block))
        counts.append(n)
        total_bytes += len(data)
    nblocks = sum(counts)
    if nblocks:
        svc = get_service_or_none()
        if svc is not None:
            crcs = await svc.crc32c_blocks(batch, block)
        else:
            flat = np.concatenate([b.reshape(-1) for b in batch])
            crcs = ec_native.crc32c_blocks(flat, block)
        crcs = np.asarray(crcs, dtype=np.uint32)
    else:
        crcs = np.zeros(0, dtype=np.uint32)
    pos = 0
    for (oid, ent, data, csum), n in zip(pend, counts):
        mine = crcs[pos:pos + n]
        pos += n
        if ec:
            # the length check already ran; every stored csum entry has
            # a freshly hashed counterpart
            if not np.array_equal(mine[:len(csum)],
                                  np.asarray(csum, dtype=np.uint32)):
                ent["corrupt"] = True
        else:
            ent["digest"] = _fold_digest(mine, len(data))
    perf.inc("bytes_hashed", total_bytes)
    perf.inc("objects_hashed", len(pend))
    perf.hist_add("digest_batch_blocks", nblocks)
    dt = time.perf_counter() - t0
    perf.hist_add("digest_batch_us", dt * 1e6)
    if progress is not None:
        progress.bytes_hashed += total_bytes
        progress.legs["digest"] += dt
    return total_bytes, nblocks


def _fold_digest(crcs: np.ndarray, total_len: int) -> int:
    """Whole-object digest from per-block crcs + true length (the tail
    block is zero-padded, so the length disambiguates). Deterministic
    pure function of (content, length): every OSD recomputes it per
    round, nothing is stored, so all replicas agree by construction."""
    from ceph_tpu.native import ec_native
    return ec_native.crc32c(
        np.asarray(crcs, dtype="<u4").tobytes()
        + int(total_len).to_bytes(8, "little"))


def _gc_rollback_generations(pg: "PGInstance") -> None:
    """Drop EC rollback generations (<oid>\\x00prev clones) whose main
    object is gone: scrub only runs on a healthy active PG with writes
    gated, so any divergence that could have needed them has already
    been resolved by peering. (Prevents deleted objects from leaking a
    prev clone forever.)"""
    from ceph_tpu.objectstore.store import Transaction
    from ceph_tpu.osd.ec_backend import PREV_SUFFIX
    store = pg.host.store
    cid = pg.backend.coll()
    live = set(pg.list_objects())
    for gh in list(store.collection_list(cid)):
        if not gh.name.endswith(PREV_SUFFIX):
            continue
        if gh.name[:-len(PREV_SUFFIX)] not in live:
            store.queue_transaction(Transaction().remove(cid, gh))


def _note_inconsistent(pg: "PGInstance", oid: str, bad_osds: list,
                       kind: str, deep: bool) -> None:
    """Register a detected inconsistency (the `list-inconsistent-obj`
    registry) and drop the flight crumb. Entries persist until a clean
    same-or-deeper round retires them, so PG_DAMAGED raises at
    detection and clears only on a verified-clean rescan."""
    flight.record("scrub_mismatch", f"pg.{pg.pgid}", oid=oid,
                  osds=list(bad_osds), kind=kind, deep=deep)
    pg.inconsistent_objects[oid] = {
        "oid": oid, "osds": sorted(bad_osds), "kind": kind,
        "deep": deep, "repaired": False,
        "pending": sorted(bad_osds), "stamp": time.time()}


def _note_repaired(pg: "PGInstance", oid: str, osd: int, ok: bool,
                   kind: str) -> None:
    flight.record("scrub_repair", f"pg.{pg.pgid}", oid=oid, osd=osd,
                  ok=ok, kind=kind)
    entry = pg.inconsistent_objects.get(oid)
    if entry is None or not ok:
        return
    entry["pending"] = [o for o in entry["pending"] if o != osd]
    if not entry["pending"]:
        entry["repaired"] = True


def turn_hold(pg: "PGInstance", primary: int) -> float:
    """How long this daemon holds its own next round back when a round
    of `primary` on `pg` has given its slot back: SCRUB_TURN_S for
    every place it stands behind that primary in the acting set, in
    the order of the ids and round again, the primary itself last.
    Daemons cannot see each other's queues; this lets the one next in
    line ask first (after one place: a member is freed with its last
    map, a little before the slowest member and the primary are) and
    the others long enough after it to find their own slot taken,
    without a round trip to be told so. No daemon is passed over for
    good, whatever its id, and nothing is drawn: a schedule explorer's
    replays see the same holds."""
    me = pg.host.whoami
    members = sorted({me, primary, *(
        o for o in pg.acting_peers() if pg.host.osdmap.is_up(o))})
    behind = (members.index(me) - members.index(primary) - 1) % len(members)
    return SCRUB_TURN_S * (behind + 1)


def retry_delay(who: int, attempt: int) -> float:
    """SCRUB_RETRY_S stretched by up to as much again
    (`reserver.retry_delay`, which backfill shares)."""
    return _stretched(SCRUB_RETRY_S, who, attempt)


class ScrubQueue:
    """The PGs an OSD is primary of, in the order their scrubs are due
    (the reference's OSD::sched_scrub queue). A PG is due `interval`
    after its last round ended, or after the queue first saw it; one
    whose reservation was rejected is put back `retry_delay` later; an
    operator's request (`request`) is due at once and goes first.
    Times are `time.monotonic()`; nothing here waits or sends."""

    class Job:
        __slots__ = ("since", "not_before", "attempts", "rounds",
                     "request")

        def __init__(self, now: float):
            self.since = now            # last round's end, or first seen
            self.not_before = 0.0       # put back until then
            self.attempts = 0           # reservations lost in a row
            self.rounds = 0             # rounds finished
            self.request = None         # (deep, future) of an operator

    def __init__(self):
        self.jobs: dict = {}            # pgid -> Job

    def sync(self, primaries, now: float) -> None:
        """`primaries`: the pgids this OSD is the active primary of. A
        PG that left takes its request with it, unanswered."""
        for pgid in set(self.jobs) - set(primaries):
            job = self.jobs.pop(pgid)
            if job.request is not None and not job.request[1].done():
                job.request[1].set_result(None)
        for pgid in primaries:
            if pgid not in self.jobs:
                self.jobs[pgid] = self.Job(now)

    def request(self, pgid, deep: bool, fut) -> None:
        job = self.jobs[pgid]
        if job.request is not None and not job.request[1].done():
            job.request[1].set_result(None)     # superseded
        job.request = (deep, fut)
        job.not_before = 0.0

    def next(self, now: float, interval: float, deep_every: int):
        """(pgid, deep) of the round to start now, or None."""
        ready = [(job.request is None, job.since, pgid)
                 for pgid, job in self.jobs.items()
                 if job.not_before <= now
                 and (job.request is not None
                      or job.since + interval <= now)]
        if not ready:
            return None
        pgid = min(ready)[2]
        job = self.jobs[pgid]
        if job.request is not None:
            return pgid, job.request[0]
        return pgid, (job.rounds + 1) % max(1, deep_every) == 0

    def asked(self) -> bool:
        """An operator is waiting for a round."""
        return any(job.request is not None for job in self.jobs.values())

    def wait(self, now: float, interval: float) -> float:
        """Seconds until some PG can be due (0: one is)."""
        return max(0.0, min(
            (job.not_before if job.request is not None
             else max(job.not_before, job.since + interval)
             for job in self.jobs.values()), default=interval) - now)

    def done(self, pgid, now: float, result: dict | None) -> None:
        """The round `next` named has ended with `result` (None: it
        died). A lost reservation puts the PG back; anything else is a
        round, and answers the operator who asked for it."""
        job = self.jobs.get(pgid)
        if job is None:
            return
        lost = result is not None and result.get("reserve_failed")
        if lost:
            job.attempts += 1
            job.not_before = now + retry_delay(
                pgid.pool * 65599 + pgid.ps, job.attempts)
            if job.request is None \
                    or job.attempts < SCRUB_REQUEST_ATTEMPTS:
                return
        else:
            job.since = now
            job.rounds += 1
        job.attempts = 0
        if job.request is not None:
            if not job.request[1].done():
                job.request[1].set_result(result)
            job.request = None


async def _reserve_acting_set(pg: "PGInstance",
                              tid: int) -> tuple[bool, list[int]]:
    """Claim one `osd_max_scrubs` slot on every up member of the acting
    set, this daemon among them, before the round may gate client
    writes (the reference's scrub reserver: OSD::sched_scrub +
    MOSDScrubReserve; the wire is `osd/reserver.py`'s, which backfill
    shares). The member with the lowest id is asked first
    and alone, the others at once when it has granted; this daemon's
    own slot is taken, not asked for, at its place in that order. And
    nobody waits for a slot: a daemon whose slots are taken says so at
    once, and the round gives back what it holds and reports
    `reserve_failed`. One first stop for all means that primaries which
    want the same daemons meet there while they hold nothing: where
    every PG spans every OSD, as on the benchmark's pool, the losers
    are turned away by the first daemon they ask and the winner is
    never crossed. The one wait left is for a peer's answer, bounded by
    `osd_scrub_reserve_timeout`."""
    host = pg.host
    reserver = getattr(host, "scrub_reserver", None)
    if reserver is None:
        return True, []
    timeout = float(_cfg(pg, "osd_scrub_reserve_timeout", 10.0))
    me = host.whoami
    mine = False                # this daemon's own slot is held
    asked: list[int] = []       # peers that may hold a slot for us

    async def ask(osd: int) -> str | None:
        """None: `osd` holds a slot for this round. Else why not."""
        if osd == me:
            nonlocal mine
            mine = reserver.slots.try_acquire()
            return None if mine else "rejected"
        return await reserver.ask(pg, tid, osd, timeout, asked)

    members = sorted({me, *(o for o in pg.acting_peers()
                            if host.osdmap.is_up(o))})
    try:
        answers = [await ask(members[0])]
        if answers[0] is None:
            answers += await asyncio.gather(*map(ask, members[1:]))
        refused = [(osd, why) for osd, why in zip(members, answers)
                   if why is not None]
        if not refused:
            return True, asked
        # given back once: a cancel that lands in the release below
        # finds nothing left for the handler at the bottom
        held, mine, asked = (mine, asked), False, []
        await _release_acting_set(pg, tid, *held)
    except BaseException:
        # a CancelledError (round reaped at daemon stop, drained round
        # interrupted) is not an Exception: without this the slots
        # collected above would leak, wedging every later round on
        # these daemons' semaphores
        await _release_acting_set(pg, tid, mine, asked)
        raise
    osd, reason = refused[0]
    stage = "local" if osd == me else f"osd.{osd}"
    scrub_perf().inc("reserve_failures")
    flight.record("scrub_reserve_fail", f"pg.{pg.pgid}", tid=tid,
                  stage=stage, reason=reason)
    dout("scrub", 4, f"pg {pg.pgid} scrub reservation on {stage} failed "
                     f"({reason}): round put back")
    return False, []


async def _release_acting_set(pg: "PGInstance", tid: int, mine: bool,
                              granted: list[int]) -> None:
    """Return the local slot (if `mine`) and every remote grant of this
    round."""
    if mine:
        pg.host.scrub_reserver.slots.release()
    await pg.host.scrub_reserver.release(pg, tid, granted)


def handle_scrub_reserve(host, pg: "PGInstance", msg):
    """An MOSDScrubReserve, decided where it is dispatched
    (`RemoteReserver.handle`): a `reserve` returns the coroutine that
    answers it, for the caller to run; `grant`, `reject` and `release`
    return None."""
    return host.scrub_reserver.handle(pg, msg)


def give_back(pg: "PGInstance", tid: int, primary: int) -> None:
    """Free the slot this daemon holds for round `tid` of `primary` on
    `pg`, if it holds one, and let its own scheduler have its turn
    (`OSD.scrub_reserver`'s `on_given_back`): what a round that ran to
    its end lets its last request say; one that ended early sends a
    `release`."""
    pg.host.scrub_reserver.give_back(pg, tid, primary)


async def scrub_pg(pg: "PGInstance", deep: bool) -> dict:
    """Primary-side scrub round, range-gated like the reference's
    chunky scrub: the namespace is walked in sorted-name ranges and
    client writes are blocked only while ONE range is being scanned,
    compared and repaired on all OSDs — between ranges the gate is
    open, so a colliding write waits out a small chunk, not the whole
    round. Publishes live progress at `pg.scrub_progress`, crumbs
    aborted rounds, and closes one `scrub_round` span whatever the
    round's end (`state`: done / reserve_failed / aborted)."""
    async with pg._scrub_lock:           # one scrub per PG at a time
        progress = ScrubProgress(pg.pgid, deep)
        pg.scrub_progress = progress
        result: dict = {}
        with tracer.span("scrub_round") as sp:
            try:
                result = await _scrub_locked(pg, deep, progress)
                return result
            except BaseException as e:
                progress.finish("aborted")
                scrub_perf().inc("aborts")
                flight.record("scrub_abort", f"pg.{pg.pgid}", deep=deep,
                              reason=f"{type(e).__name__}: {e}")
                raise
            finally:
                if progress.state == "scrubbing":
                    progress.finish()
                if sp is not None:
                    sp.tags.update(
                        pgid=progress.pgid, deep=deep,
                        state=progress.state,
                        objects=progress.objects_total,
                        bytes=progress.bytes_hashed,
                        errors=result.get("errors", 0),
                        repaired=result.get("repaired", 0),
                        **{f"{leg}_us": round(sec * 1e6, 1)
                           for leg, sec in progress.legs.items()})


def _plan_ranges(oids: list, chunk_max: int) -> list:
    """Partition the whole name space into `(lo, hi]` ranges with a
    boundary every `chunk_max` names of the primary's sorted listing.
    First range starts at None and last ends at None: peer-only names
    (strays the primary never listed) sort into SOME range and are
    still compared, which is what majority-delete detection needs."""
    bounds = [oids[i] for i in range(chunk_max - 1, len(oids), chunk_max)]
    if bounds and bounds[-1] == oids[-1]:
        bounds.pop()                     # tail range is open-ended anyway
    ranges, lo = [], None
    for b in bounds:
        ranges.append((lo, b))
        lo = b
    ranges.append((lo, None))
    return ranges


async def _scrub_range(pg: "PGInstance", deep: bool, oid_range,
                       progress: "ScrubProgress",
                       release: tuple | None = None) -> dict:
    """Gather this range's maps from self + up acting peers and
    compare/repair it. Caller holds the write gate, so the slice is
    frozen across all OSDs while it is judged. `release` = (tid, peers)
    on the round's last range: the request tells each of `peers` to
    give its slot of reservation `tid` back when its map is sent, and
    takes it off the list of those that still need a message for it."""
    host = pg.host
    t0, digest0 = time.perf_counter(), progress.legs["digest"]
    maps: dict[int, dict] = {
        host.whoami: await build_scrub_map(pg, deep, progress,
                                           oid_range=oid_range,
                                           paced=False)}
    tid = pg.backend.new_tid()
    waits = []
    for peer in sorted(pg.acting_peers()):
        if not host.osdmap.is_up(peer):
            continue
        fut = asyncio.get_running_loop().create_future()
        pg._scrub_waiters[(tid, peer)] = fut
        req = {"pgid": [pg.pgid.pool, pg.pgid.ps], "tid": tid,
               "from": host.whoami, "deep": deep, "range": list(oid_range)}
        if release is not None and peer in release[1]:
            req["release"] = release[0]
        try:
            await host.send_osd(peer, MOSDRepScrub(req))
            waits.append((peer, fut))
            if "release" in req:
                release[1].remove(peer)
        except Exception as e:
            dout("scrub", 2, f"scrub request to osd.{peer} failed: {e}")
            fut.cancel()
            pg._scrub_waiters.pop((tid, peer), None)
    for peer, fut in waits:
        try:
            maps[peer] = await asyncio.wait_for(fut, SCRUB_PEER_TIMEOUT)
        except asyncio.TimeoutError:
            dout("scrub", 2, f"osd.{peer} never sent a scrub map")
            flight.record("scrub_abort", f"pg.{pg.pgid}", deep=deep,
                          reason="peer_timeout", peer=peer)
        finally:
            pg._scrub_waiters.pop((tid, peer), None)

    # the scan is this OSD's and then the wait for the slowest peer's;
    # the primary's own digest batch is a leg of its own
    t1 = time.perf_counter()
    progress.legs["scan"] += t1 - t0 - (progress.legs["digest"] - digest0)
    if pg.pool.type == "erasure":
        res = await _compare_repair_ec(pg, maps, deep)
    else:
        res = await _compare_repair_replicated(pg, maps, deep)
    progress.legs["compare"] += time.perf_counter() - t1
    res["osds"] = sorted(maps)
    return res


async def _scrub_locked(pg: "PGInstance", deep: bool,
                        progress: "ScrubProgress") -> dict:
    host = pg.host
    t0 = time.monotonic()
    oids = sorted(pg.list_objects())
    progress.objects_total = len(oids)
    chunk_max = max(1, int(_cfg(pg, "osd_scrub_chunk_max", 32)))
    sleep_s = float(_cfg(pg, "osd_scrub_sleep", 0.0))
    ranges = _plan_ranges(oids, chunk_max)

    result: dict = {"errors": 0, "repaired": 0,
                    "inconsistent": [], "unrepaired": []}
    seen_osds = {host.whoami}

    # reserve one scrub slot per acting-set member for the WHOLE round
    # (sched_scrub's reserver): osd_max_scrubs bounds concurrent rounds
    # per daemon cluster-wide, and a rejected reservation ends the
    # round before any write gate was ever taken
    reserve_tid = pg.backend.new_tid()
    reserved, reserved_peers = False, []
    if bool(_cfg(pg, "osd_scrub_reserve", True)):
        t_res = time.perf_counter()
        ok, reserved_peers = await _reserve_acting_set(pg, reserve_tid)
        progress.legs["reserve"] = time.perf_counter() - t_res
        reserved = ok and getattr(host, "scrub_reserver",
                                  None) is not None
        if not ok:
            progress.finish("reserve_failed")
            result.update({"reserve_failed": True, "deep": deep,
                           "osds": sorted(seen_osds), "objects": 0,
                           "bytes_hashed": 0, "duration_s": round(
                               time.monotonic() - t0, 3), "mb_s": 0.0})
            return result
    try:
        for i, rng in enumerate(ranges):
            # pace UNGATED: while scrub waits for its dmclock turn (and
            # between ranges) client writes flow freely — this is where
            # the QoS class actually shapes scrub against foreground
            # load
            t_wait = time.perf_counter()
            await _qos_grant(pg)
            await pg.block_writes()
            progress.legs["grant_wait"] += time.perf_counter() - t_wait
            last = reserved and i + 1 == len(ranges)
            try:
                r = await _scrub_range(
                    pg, deep, rng, progress,
                    (reserve_tid, reserved_peers) if last else None)
            finally:
                pg.unblock_writes()
            result["errors"] += r["errors"]
            result["repaired"] += r["repaired"]
            result["inconsistent"].extend(r["inconsistent"])
            result["unrepaired"].extend(r.get("unrepaired", []))
            seen_osds.update(r["osds"])
            if sleep_s > 0 and i + 1 < len(ranges):
                await asyncio.sleep(sleep_s)
    finally:
        if reserved:
            await _release_acting_set(pg, reserve_tid, True, reserved_peers)
            host.scrub_slot_freed(turn_hold(pg, host.whoami))

    result["deep"] = deep
    result["osds"] = sorted(seen_osds)
    result["objects"] = progress.objects_total
    result["bytes_hashed"] = progress.bytes_hashed
    dt = max(1e-9, time.monotonic() - t0)
    result["duration_s"] = round(dt, 3)
    result["mb_s"] = round(progress.bytes_hashed / dt / 2**20, 2)
    pg.last_scrub = result
    now = time.time()
    pg.last_scrub_stamp = now
    if deep:
        pg.last_deep_scrub_stamp = now

    # a clean same-or-deeper round retires registry entries: the
    # damage is VERIFIED gone, so the mgr health checks can clear
    found = set(result["inconsistent"])
    for oid in list(pg.inconsistent_objects):
        entry = pg.inconsistent_objects[oid]
        if oid not in found and (deep or not entry.get("deep")):
            del pg.inconsistent_objects[oid]

    perf = scrub_perf()
    perf.inc("rounds")
    if deep:
        perf.inc("deep_rounds")
    if result["errors"]:
        perf.inc("errors_found", result["errors"])
    if result["repaired"]:
        perf.inc("errors_repaired", result["repaired"])
    if result.get("unrepaired"):
        perf.inc("errors_unrepaired", len(result["unrepaired"]))
    st = pg.scrub_stats
    st["objects_scrubbed"] += progress.objects_total
    st["bytes_hashed"] += progress.bytes_hashed
    st["errors_found"] += result["errors"]
    st["errors_repaired"] += result["repaired"]

    dout("scrub", 2 if result["errors"] else 4,
         f"pg {pg.pgid} {'deep-' if deep else ''}scrub: "
         f"{result['errors']} errors, {result['repaired']} repaired, "
         f"{result['objects']} objects, {result['mb_s']} MB/s hashed")
    return result


async def _compare_repair_ec(pg: "PGInstance", maps: dict,
                             deep: bool) -> dict:
    """Each EC shard self-certifies via its stored per-chunk crc; a
    corrupt or stale shard is reconstructed from the survivors
    (ECBackend.cc:1092 deep verify; repair via RecoveryOp). Presence
    votes: when a majority of the acting set lacks the object, the
    straggler shards are a half-deleted object and are removed."""
    errors = repaired = 0
    inconsistent: list[str] = []
    me = pg.host.whoami
    oids = sorted({o for m in maps.values() for o in m})
    for oid in oids:
        holders = [osd for osd, m in maps.items() if oid in m]
        absent = [osd for osd in maps if oid not in maps[osd]]
        if len(absent) > len(maps) / 2:
            # majority says the object is gone: finish the deletion
            errors += len(holders)
            inconsistent.append(oid)
            _note_inconsistent(pg, oid, holders, "stray", deep)
            for osd in holders:
                try:
                    if osd == me:
                        pg.backend.local_apply(oid, "delete", b"")
                    else:
                        await pg.send_push(osd, oid, b"", None,
                                           delete=True)
                    repaired += 1
                    _note_repaired(pg, oid, osd, True, "stray")
                except Exception as e:
                    _note_repaired(pg, oid, osd, False, "stray")
                    dout("scrub", 1, f"stray delete of {oid} on "
                                     f"osd.{osd} failed: {e}")
            continue
        newest = max((tuple(maps[osd][oid]["version"]) for osd in holders
                      if not maps[osd][oid]["corrupt"]), default=None)
        bad: list[int] = []
        for osd, m in maps.items():
            ent = m.get(oid)
            if ent is None or ent["corrupt"] or (
                    newest is not None
                    and tuple(ent["version"]) != newest):
                bad.append(osd)
        if not bad:
            continue
        errors += len(bad)
        inconsistent.append(oid)
        _note_inconsistent(pg, oid, bad, "shard", deep)
        for osd in bad:
            try:
                if osd == me:
                    await pg.backend.pull_object(None, oid, None)
                else:
                    await pg.backend.push_object(osd, oid)
                repaired += 1
                _note_repaired(pg, oid, osd, True, "shard")
            except Exception as e:
                _note_repaired(pg, oid, osd, False, "shard")
                dout("scrub", 1, f"repair of {oid} shard on osd.{osd} "
                                 f"failed: {type(e).__name__} {e}")
    return {"errors": errors, "repaired": repaired,
            "inconsistent": inconsistent}


async def _compare_repair_replicated(pg: "PGInstance", maps: dict,
                                     deep: bool) -> dict:
    """Strict-majority authoritative selection (be_select_auth_object):
    copies disagreeing with the majority fingerprint — including absent
    copies, which vote — are overwritten (or deleted) toward it. No
    strict majority means the inconsistency is reported but NOT
    repaired: guessing could propagate rot (the reference leaves
    ambiguous objects to `ceph pg repair` policy for the same reason)."""
    errors = repaired = 0
    inconsistent: list[str] = []
    unrepaired: list[str] = []
    me = pg.host.whoami
    oids = sorted({o for m in maps.values() for o in m})
    for oid in oids:
        def fingerprint(ent):
            if ent is None:
                return ABSENT
            if ent["corrupt"]:
                return None         # self-certified bad: no vote
            key = [ent["size"], ent["attr_digest"]]
            if deep:
                key += [ent.get("digest"), ent.get("omap_digest")]
            return tuple(key)

        prints = {osd: fingerprint(m.get(oid)) for osd, m in maps.items()}
        tally: dict = {}
        for osd, fp in prints.items():
            if fp is not None:
                tally.setdefault(fp, []).append(osd)
        bad_by_corruption = [osd for osd, fp in prints.items()
                             if fp is None]
        if not tally:
            unrepaired.append(oid)      # unreadable everywhere
            errors += len(prints)
            _note_inconsistent(pg, oid, list(prints), "unreadable", deep)
            continue
        auth_fp, auth_osds = max(tally.items(), key=lambda kv: len(kv[1]))
        majority = len(auth_osds) > len(prints) / 2
        bad = [osd for osd, fp in prints.items() if fp != auth_fp]
        if not bad:
            continue
        errors += len(bad)
        inconsistent.append(oid)
        _note_inconsistent(pg, oid, bad, "copy", deep)
        if not majority and not (len(tally) == 1 and bad_by_corruption):
            # a corrupt copy may be repaired toward the only candidate
            # even without strict majority; a tie between two VALID
            # fingerprints is never guessed at
            unrepaired.append(oid)
            dout("scrub", 1, f"pg {pg.pgid} {oid}: no majority "
                             f"fingerprint ({prints}); NOT auto-repairing")
            continue
        try:
            if auth_fp == ABSENT:
                # the delete is authoritative: finish it on the holders.
                # The push carries the primary's snapshot state so a
                # delete-repair can't wipe legitimate clones the target
                # replica holds (head deletes preserve clones)
                snap_state = pg.backend.snap_state_for_push(oid)
                for osd in bad:
                    if osd == me:
                        pg.backend.local_apply(oid, "delete", b"")
                    else:
                        await pg.send_push(osd, oid, b"", None,
                                           delete=True,
                                           snap_state=snap_state)
                    repaired += 1
                    _note_repaired(pg, oid, osd, True, "copy")
                continue
            if me in bad:
                # the primary's own copy is wrong: adopt an authoritative
                # peer's before pushing
                await pg.pull_transport(auth_osds[0], oid)
                repaired += 1
                _note_repaired(pg, oid, me, True, "copy")
                bad.remove(me)
            for osd in bad:
                await pg.backend.push_object(osd, oid)
                repaired += 1
                _note_repaired(pg, oid, osd, True, "copy")
        except Exception as e:
            dout("scrub", 1, f"repair of {oid} failed: "
                             f"{type(e).__name__} {e}")
    return {"errors": errors, "repaired": repaired,
            "inconsistent": inconsistent, "unrepaired": unrepaired}
