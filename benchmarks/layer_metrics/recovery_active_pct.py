"""Share of the window's whole seconds in which a shard was rebuilt (an
`ec_recover` span that names its object ended): how much of the window
the clients shared with recovery or backfill."""
from benchmarks.layer_metrics import recovery_spans

NAME = "recovery_active_pct"
UNIT = "%"
LAYER = "osd/pg+osd/ec_backend"
MOVES = "ops_s"


def read(ctx):
    active = recovery_spans.active_seconds(ctx)
    if active is None:
        return None
    return 100.0 * len(active) / recovery_spans.seconds(ctx)[1]
