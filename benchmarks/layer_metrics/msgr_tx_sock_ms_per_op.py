"""Loop CPU an op in `writer.writelines(parts)` of the write loop (asyncio
tries the `sendmsg` inline) and in the selector's `_write_ready` after a
partial send."""
from benchmarks.layer_metrics import loop_parts

NAME = "msgr_tx_sock_ms_per_op"
UNIT = "ms/op"
LAYER = "msg/messenger"
MOVES = "ops_s"


def read(ctx):
    return loop_parts.ms_per_op(ctx, "msgr.tx_sock")
