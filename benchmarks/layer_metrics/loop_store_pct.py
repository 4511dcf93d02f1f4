"""Share of the loop's wall time (`loop_slice.store_us`) in the object
stores: `store_commit` on eleven OSDs."""
from benchmarks.layer_metrics import loop_share

NAME = "loop_store_pct"
UNIT = "%"
LAYER = "objectstore"
MOVES = "ops_s"


def read(ctx):
    return loop_share.share(ctx, "store")
