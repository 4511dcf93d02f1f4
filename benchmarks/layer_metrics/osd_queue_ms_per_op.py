"""Loop CPU an op in `utils/work_queue.py` outside any span: the op
queue's workers, the key windows, the dispatch-delay hold's wake-ups."""
from benchmarks.layer_metrics import loop_parts

NAME = "osd_queue_ms_per_op"
UNIT = "ms/op"
LAYER = "osd/pg+osd/ec_backend"
MOVES = "ops_s"


def read(ctx):
    return loop_parts.ms_per_op(ctx, "osd.queue")
