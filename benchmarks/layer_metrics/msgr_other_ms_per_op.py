"""Loop CPU an op in the rest of the `msgr` label: keepalive, handshake,
accept, the connection's run loop. Charged, never computed by
subtraction."""
from benchmarks.layer_metrics import loop_parts

NAME = "msgr_other_ms_per_op"
UNIT = "ms/op"
LAYER = "msg/messenger"
MOVES = "ops_s"


def read(ctx):
    return loop_parts.ms_per_op(ctx, "msgr.other")
