"""Median wait of a PG for its backfill slots, among the reservations
that were granted: `local_us` (this primary's own slot, waited for in
turn) and `remote_us` (the targets' answers and the waits between
attempts) of a `backfill_reserve` span. While it waits the PG's lost
shards stay lost and a write to one of them rebuilds it first."""
import statistics

from benchmarks.layer_metrics import recovery_spans

NAME = "backfill_reserve_wait_ms"
UNIT = "ms"
LAYER = "osd/reserver"
MOVES = "op_p95_ms"


def read(ctx):
    granted = recovery_spans.reserves(ctx, "granted")
    if not granted:
        return None
    return statistics.median(
        s["tags"]["local_us"] + s["tags"]["remote_us"]
        for s in granted) / 1e3
