"""PGs whose recovery or backfill ran to its end inside the window:
`backfill_done` markers of `state` done. One PG may count once an
interval."""
from benchmarks.layer_metrics import recovery_spans

NAME = "backfill_pgs_done"
UNIT = "pgs"
LAYER = "osd/reserver"
MOVES = "ops_s"


def read(ctx):
    if not recovery_spans.dones(ctx):
        return None
    return float(len(recovery_spans.dones(ctx, "done")))
