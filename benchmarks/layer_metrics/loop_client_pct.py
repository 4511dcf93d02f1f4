"""Share of the loop's wall time (`loop_slice.client_us`) in the client
library: `rados_op`, `aio_op`, replies handed to a client."""
from benchmarks.layer_metrics import loop_share

NAME = "loop_client_pct"
UNIT = "%"
LAYER = "rados/client"
MOVES = "ops_s"


def read(ctx):
    return loop_share.share(ctx, "client")
