"""Loop CPU an op in the steps of `Connection._dispatch_loop` outside a
handler: the queue's `get`, the ack bookkeeping, the idle-ack timer."""
from benchmarks.layer_metrics import loop_parts

NAME = "msgr_dispatch_ms_per_op"
UNIT = "ms/op"
LAYER = "msg/messenger"
MOVES = "ops_s"


def read(ctx):
    return loop_parts.ms_per_op(ctx, "msgr.dispatch")
