"""Loop CPU an op in the self time of `ec_read`, `ec_encode`, `ec_decode`,
`ec_write`, `ec_recover`: gather bookkeeping, assemble, stack, concat."""
from benchmarks.layer_metrics import loop_parts

NAME = "osd_ec_ms_per_op"
UNIT = "ms/op"
LAYER = "osd/pg+osd/ec_backend"
MOVES = "ops_s"


def read(ctx):
    return loop_parts.ms_per_op(ctx, "osd.ec")
