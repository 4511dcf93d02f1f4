"""MESSAGE frames written to the wire by all messengers, per client op."""
NAME = "msgr_frames_per_op"
UNIT = "frames/op"
LAYER = "msg/messenger"
MOVES = "ops_s"


def read(ctx):
    if not ctx.ops:
        return None
    return ctx.delta("msgr", "frames_tx") / ctx.ops
