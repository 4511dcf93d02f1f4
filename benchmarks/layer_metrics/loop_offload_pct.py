"""Share of the loop's wall time (`loop_slice.offload_us`) in the batcher's
on-loop half: linger, flush, `offload_batch` round the staged dispatch."""
from benchmarks.layer_metrics import loop_share

NAME = "loop_offload_pct"
UNIT = "%"
LAYER = "offload/service"
MOVES = "ops_s"


def read(ctx):
    return loop_share.share(ctx, "offload")
