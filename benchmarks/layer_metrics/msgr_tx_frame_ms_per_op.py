"""Loop CPU an op in the steps of `Connection._write_loop` outside the
codec and the send: `_coalesce` and its turns, `encode_segments`, `Frame`
building, `_take_ack`, the counters."""
from benchmarks.layer_metrics import loop_parts

NAME = "msgr_tx_frame_ms_per_op"
UNIT = "ms/op"
LAYER = "msg/messenger"
MOVES = "ops_s"


def read(ctx):
    return loop_parts.ms_per_op(ctx, "msgr.tx_frame")
