"""Loop CPU an op in the self time of `ms_dispatch` on an OSD: a shard OSD
serving a sub-op read or write, the primary taking their replies."""
from benchmarks.layer_metrics import loop_parts

NAME = "osd_subop_ms_per_op"
UNIT = "ms/op"
LAYER = "osd/pg+osd/ec_backend"
MOVES = "ops_s"


def read(ctx):
    return loop_parts.ms_per_op(ctx, "osd.subop")
