"""Share of the loop's wall time (`loop_slice.harness_us`) in the benchmark's
own generator and comparison, which run on the measured loop."""
from benchmarks.layer_metrics import loop_share

NAME = "loop_harness_pct"
UNIT = "%"
LAYER = "benchmark harness on the measured loop"
MOVES = "ops_s"


def read(ctx):
    return loop_share.share(ctx, "harness")
