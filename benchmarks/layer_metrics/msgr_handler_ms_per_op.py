"""Loop CPU an op in handlers of messages that carry no trace context
(sent from a timer or a daemon's own loop: `MPing`, `MPingReply`,
`MMgrReport`, `MLog`, map traffic), run by the dispatch loop."""
from benchmarks.layer_metrics import loop_parts

NAME = "msgr_handler_ms_per_op"
UNIT = "ms/op"
LAYER = "msg/messenger"
MOVES = "ops_s"


def read(ctx):
    return loop_parts.ms_per_op(ctx, "msgr.handler")
