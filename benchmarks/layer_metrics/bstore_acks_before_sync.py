"""Transaction contexts whose callbacks were delivered before the group
commit that covers them had finished (`bstore_txc.ran_ahead`): the
guarantee's reading, 0. A reading and not a limit of the comparison
that decides `correct`, as `scrub_errors_found` is."""
from benchmarks.layer_metrics import bstore_spans

NAME = "bstore_acks_before_sync"
UNIT = "count"
LAYER = "objectstore"
MOVES = "op_p95_ms"


def read(ctx):
    txcs = bstore_spans.txcs(ctx)
    if not txcs:
        return None
    return float(sum(bool(t["tags"]["ran_ahead"]) for t in txcs))
