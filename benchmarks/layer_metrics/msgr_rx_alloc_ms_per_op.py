"""Loop CPU an op in `bytearray(n)` of `Endpoint._read_body`: a large
body's buffer, zero-filled."""
from benchmarks.layer_metrics import loop_parts

NAME = "msgr_rx_alloc_ms_per_op"
UNIT = "ms/op"
LAYER = "msg/messenger"
MOVES = "ops_s"


def read(ctx):
    return loop_parts.ms_per_op(ctx, "msgr.rx_alloc")
