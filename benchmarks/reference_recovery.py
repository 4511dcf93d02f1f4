"""The plain reference for recovery and backfill of an erasure pool:
what has to be rebuilt where after the acting sets change, what each
rebuilt shard has to hold, and the rule the rebuilding is throttled by.

It imports nothing of the program: the shards are
`benchmarks.reference`'s, the decoding `benchmarks.reference_decode`'s
Gauss-Jordan elimination, and the acting sets are data it is handed (it
rebuilds no CRUSH map). Upstream's words for the rule
(doc/dev/osd_internals/backfill_reservation.rst): a PG backfills only
while it holds one of `osd_max_backfills` slots on its primary and one
on each backfill target.

An interval is a stretch in which no PG's acting set changes. A PG's
acting set lists one OSD a shard position, or None where the position
has no OSD (its OSD is down and not yet out). A write is acknowledged by
every position of its PG that has an OSD in the interval it is made
in; so an OSD holds position p of an object if it stood at p in that
PG when the object was written, or has been rebuilt it since.
"""
from __future__ import annotations

import numpy as np

from benchmarks import reference
from benchmarks.reference_decode import reconstruct

ROLES = ("local", "remote")


def rebuild_plan(intervals: list[dict]) -> list[set[tuple]]:
    """For each interval the (object, position, target) that have to be
    rebuilt in it for every PG to be whole on its acting set.
    `intervals[i]` is `{"acting": {pg: [osd or None, ...]}, "written":
    {pg: [object, ...]}}`: the acting sets of interval i and the objects
    acknowledged in it, a PG without writes may be left out of
    `written`. It is taken that every interval's rebuilding ran to its
    end before the next began; the first interval starts from empty
    stores, so it has nothing to rebuild."""
    holds: dict[tuple, set] = {}        # (pg, position, osd) -> objects
    known: dict = {}                    # pg -> objects written so far
    plan = []
    for interval in intervals:
        todo = set()
        for pg, acting in interval["acting"].items():
            for pos, osd in enumerate(acting):
                if osd is None:
                    continue
                have = holds.setdefault((pg, pos, osd), set())
                todo |= {(oid, pos, osd)
                         for oid in known.get(pg, set()) - have}
                have |= known.get(pg, set())
        for pg, oids in interval.get("written", {}).items():
            known.setdefault(pg, set()).update(oids)
            for pos, osd in enumerate(interval["acting"][pg]):
                if osd is not None:
                    holds.setdefault((pg, pos, osd), set()).update(oids)
        plan.append(todo)
    return plan


def apply_row(value_rows: np.ndarray, k: int, m: int, position: int
              ) -> np.ndarray:
    """Row `position` of the k+m shards of the (k, n) data rows: the row
    itself, or a row of the coding matrix applied by shifts and xors."""
    if position < k:
        return value_rows[position].copy()
    coeffs = reference.reed_sol_van_matrix(k, m)[position - k]
    out = np.zeros(value_rows.shape[1], dtype=np.uint8)
    for j, c in enumerate(coeffs):
        out ^= reference.gf_mul(int(c), value_rows[j])
    return out


def rebuilt_shard(value: bytes, k: int, m: int, chunk: int, position: int,
                  survivors) -> np.ndarray:
    """What a target has to hold for `position` of an object of `value`
    after it was rebuilt from `survivors` (the other positions that
    hold the object): computed once as the row of
    `reference.expected_shards` and once by decoding the k lowest
    survivors and encoding the position again. The two ways share the
    field and nothing else; where they differ the reference itself is
    wrong, and that is an error."""
    want = reference.expected_shards(value, k, m, chunk)
    use = sorted(set(survivors) - {position})[:k]
    if len(use) < k:
        raise ValueError(f"rebuilt_shard: {len(use)} survivors beside "
                         f"position {position}, need {k}")
    data = reconstruct({j: want[j] for j in use}, k, m)
    again = apply_row(data, k, m, position)
    if not np.array_equal(again, want[position]):
        raise AssertionError(f"rebuilt_shard: position {position} from "
                             f"{use} is not the encoder's row")
    return want[position]


def shard_differs(have: bytes, value: bytes, k: int, m: int, chunk: int,
                  position: int, survivors) -> int:
    """Bytes of a rebuilt shard as a target holds it that differ from
    the reference's (a length that differs counts whole)."""
    want = rebuilt_shard(value, k, m, chunk, position, survivors)
    got = np.frombuffer(have, dtype=np.uint8)
    if got.size != want.size:
        return max(got.size, want.size)
    return int(np.count_nonzero(got != want))


def check_reservations(events, max_backfills: int) -> list[tuple]:
    """The rule as a checker: `events` are (time, osd, role, +1 or -1),
    a slot of `role` (`local`: the primary's own; `remote`: a target's)
    taken or given back on `osd`. Returns the (time, osd, role, held)
    at which an OSD held more than `max_backfills` in one role, or
    gave back what it did not hold; empty where the rule was kept. Of
    two events at one instant the giving back comes first."""
    held: dict[tuple, int] = {}
    broken = []
    for t, osd, role, delta in sorted(events, key=lambda e: (e[0], e[3])):
        if role not in ROLES or delta not in (1, -1):
            raise ValueError(f"check_reservations: event "
                             f"{(t, osd, role, delta)!r}")
        n = held[(osd, role)] = held.get((osd, role), 0) + delta
        if n > max_backfills or n < 0:
            broken.append((t, osd, role, n))
    return broken
