"""The loop's CPU an op: every label of the `loop_slice` spans but `idle`,
over the ops completed, in ms. With 16 ops always in flight it is the
reciprocal of `ops_s`, and the whole of which the parts are shares."""
from benchmarks.layer_metrics import loop_parts

NAME = "loop_cpu_ms_per_op"
UNIT = "ms/op"
LAYER = "event loop (all daemons)"
MOVES = "ops_s"


def read(ctx):
    return loop_parts.busy_ms_per_op(ctx)
