"""Share of the object bytes the stores handed out that left as
read-only windows on the buffers they keep (`store_read` in the copy
ledger: referenced over referenced + copied, deltas over the window).
Copied is a read of an object the store has made private, returned as
`bytes`."""
from benchmarks.layer_metrics.store_direct import direct_pct

NAME = "store_read_direct_pct"
UNIT = "%"
LAYER = "objectstore"
MOVES = "ops_s"


def read(ctx):
    return direct_pct(ctx, "store_read")
