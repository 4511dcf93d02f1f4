"""Loop CPU an op that is the observer itself: spans, sections, the
callback hook and the closing of slices (`loop_slice.instr.by_kind`,
summed). A part OF `loop_cpu_ms_per_op`, not beside it."""
from benchmarks.layer_metrics import loop_instr

NAME = "instr_ms_per_op"
UNIT = "ms/op"
LAYER = "event loop (all daemons)"
MOVES = "ops_s"


def read(ctx):
    return loop_instr.ms_per_op(ctx, "by_kind")
