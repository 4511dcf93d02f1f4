"""Share of the loop's wall time (`loop_slice.msgr_us`) in messenger code:
framing, the socket transport's reads and writes, `ms_send`, and
`ms_dispatch` where its service names no daemon."""
from benchmarks.layer_metrics import loop_share

NAME = "loop_msgr_pct"
UNIT = "%"
LAYER = "msg/messenger"
MOVES = "ops_s"


def read(ctx):
    return loop_share.share(ctx, "msgr")
