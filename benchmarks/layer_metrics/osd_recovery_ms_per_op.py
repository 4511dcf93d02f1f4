"""Loop CPU an op in the self time of `ec_recover` and `backfill_reserve`
and in the reservation's handler on a target: what recovery's own code
takes of the loop beside the writes, over the writes completed. Its
gathers, decodes and pushes are charged where a client's are (`osd.ec`,
`offload`, `msgr`)."""
from benchmarks.layer_metrics import loop_parts

NAME = "osd_recovery_ms_per_op"
UNIT = "ms/op"
LAYER = "osd/pg+osd/ec_backend"
MOVES = "ops_s"


def read(ctx):
    return loop_parts.ms_per_op(ctx, "osd.recovery")
