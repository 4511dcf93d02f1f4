"""Deferred extents landed for each sync of a block file that landed
them: `ops` of the window's `bstore_deferred_flush` spans over their
number. Upstream's batch is 64; one an extent is a sync a write, the
cost the path exists to avoid."""
from benchmarks.layer_metrics import deferred_spans

NAME = "bstore_deferred_ops_per_flush"
UNIT = "ops/flush"
LAYER = "objectstore"
MOVES = "ops_s"


def read(ctx):
    flushes = deferred_spans.flushes(ctx)
    if not flushes:
        return None
    return sum(f["ops"] for f in flushes) / len(flushes)
