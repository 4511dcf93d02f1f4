"""ACK, KEEPALIVE and KEEPALIVE_ACK frames all messengers framed
(`ctrl_frames_tx`), per client op: the frames that carry neither data
nor an op, which `msgr_frames_per_op` (MESSAGE frames only) does not
count."""
from benchmarks.layer_metrics import msgr_ctrl

NAME = "msgr_ctrl_frames_per_op"
UNIT = "frames/op"
LAYER = "msg/messenger"
MOVES = "ops_s"


def read(ctx):
    got = msgr_ctrl.deltas(ctx)
    if got is None or not ctx.ops:
        return None
    return got[0] / ctx.ops
