"""Spans closed on the loop an op (`loop_slice.instr.spans`): each costs
the loop its life outside its body, under full tracing."""
from benchmarks.layer_metrics import loop_instr

NAME = "spans_per_op"
UNIT = "spans/op"
LAYER = "event loop (all daemons)"
MOVES = "ops_s"


def read(ctx):
    return loop_instr.count_per_op(ctx, "spans")
