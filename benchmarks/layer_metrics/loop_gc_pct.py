"""Share of the loop's wall time (`loop_slice.gc_us`) in the cyclic
collector, taken out of whichever label it interrupted."""
from benchmarks.layer_metrics import loop_share

NAME = "loop_gc_pct"
UNIT = "%"
LAYER = "event loop (all daemons)"
MOVES = "op_p95_ms"


def read(ctx):
    return loop_share.share(ctx, "gc")
