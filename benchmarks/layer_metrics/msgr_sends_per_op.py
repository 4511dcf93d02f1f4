"""Calls of all messengers' write loops into the transport's
`writelines` (`tx_sends`: one `sendmsg` each unless the socket is full),
per client op: what a frame costs in system calls once frames bound for
one peer share a send."""
from benchmarks.layer_metrics import msgr_ctrl

NAME = "msgr_sends_per_op"
UNIT = "sends/op"
LAYER = "msg/messenger"
MOVES = "ops_s"


def read(ctx):
    got = msgr_ctrl.deltas(ctx)
    if got is None or not ctx.ops:
        return None
    return got[1] / ctx.ops
