"""Share of the loop's wall time (`loop_slice.unattributed_us`) the account
could put down to no layer: what the table of layers does not cover."""
from benchmarks.layer_metrics import loop_share

NAME = "loop_unattributed_pct"
UNIT = "%"
LAYER = "event loop (all daemons)"
MOVES = "ops_s"


def read(ctx):
    return loop_share.share(ctx, "unattributed")
