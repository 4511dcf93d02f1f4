"""Loop CPU an op in the frame codec's native calls: `verify_body` on the
receive side, `crcs` and `pack` on the send side (the Python codec's
`crc32c` loops where that one is active)."""
from benchmarks.layer_metrics import loop_parts

NAME = "msgr_codec_ms_per_op"
UNIT = "ms/op"
LAYER = "msg/messenger"
MOVES = "ops_s"


def read(ctx):
    return loop_parts.ms_per_op(ctx, "msgr.codec")
