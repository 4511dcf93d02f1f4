"""Shards rebuilt a second while recovery ran: `ec_recover` spans that
ended in the seconds `recovery_active_pct` counts, over those seconds.
One span is one object's shard for one target (512 KiB on the cell's
pool)."""
from benchmarks.layer_metrics import recovery_spans

NAME = "recovery_objects_s"
UNIT = "objects/s"
LAYER = "osd/pg+osd/ec_backend"
MOVES = "ops_s"


def read(ctx):
    active = recovery_spans.active_seconds(ctx)
    if not active:
        return None
    counts = recovery_spans.per_second(ctx, "ec_recover")
    return sum(counts[i] for i in active) / len(active)
