"""Loop CPU an op in the self time of `osd_op` and `pg_op`: the op's turn
in the PG (checks, PG log, `do_op`)."""
from benchmarks.layer_metrics import loop_parts

NAME = "osd_pg_ms_per_op"
UNIT = "ms/op"
LAYER = "osd/pg+osd/ec_backend"
MOVES = "ops_s"


def read(ctx):
    return loop_parts.ms_per_op(ctx, "osd.pg")
