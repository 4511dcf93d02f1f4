"""The clients' rate beside recovery as a share of their rate without it:
`rados_op` spans that ended a second, in the seconds
`recovery_active_pct` counts over the window's other whole seconds.
None where either kind has fewer than two seconds."""
from benchmarks.layer_metrics import recovery_spans

NAME = "client_rate_in_recovery_pct"
UNIT = "%"
LAYER = "osd/pg+osd/ec_backend"
MOVES = "ops_s"


def read(ctx):
    active = recovery_spans.active_seconds(ctx)
    if active is None:
        return None
    counts = recovery_spans.per_second(ctx, "rados_op")
    quiet = set(range(len(counts))) - active
    if len(active) < 2 or len(quiet) < 2:
        return None
    beside = sum(counts[i] for i in active) / len(active)
    alone = sum(counts[i] for i in quiet) / len(quiet)
    return 100.0 * beside / alone if alone else None
