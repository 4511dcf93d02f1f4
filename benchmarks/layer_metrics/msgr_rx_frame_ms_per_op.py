"""Loop CPU an op in the steps of `Connection._read_loop`: `Frame.read`'s
three `readexactly`, the copies out of the spill, the segments' windows,
`Message.decode_segments` (the `json` parse), seq accounting, `_trim_sent`."""
from benchmarks.layer_metrics import loop_parts

NAME = "msgr_rx_frame_ms_per_op"
UNIT = "ms/op"
LAYER = "msg/messenger"
MOVES = "ops_s"


def read(ctx):
    return loop_parts.ms_per_op(ctx, "msgr.rx_frame")
