"""Share of the control frames (ACK, KEEPALIVE, KEEPALIVE_ACK) that left
in a send which also carried a MESSAGE frame (`ctrl_rode_tx` over
`ctrl_frames_tx`): how often a frame that carries nothing cost no
`sendmsg` of its own."""
from benchmarks.layer_metrics import msgr_ctrl

NAME = "msgr_ctrl_rode_pct"
UNIT = "%"
LAYER = "msg/messenger"
MOVES = "ops_s"


def read(ctx):
    got = msgr_ctrl.deltas(ctx)
    if got is None or not ctx.ops or got[0] <= 0:
        return None
    return 100.0 * got[1] / got[0]
