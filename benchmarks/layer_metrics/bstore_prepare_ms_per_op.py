"""What the event loop still pays the stores for a client op: the
`prepare_us` of every transaction context in the window (ops applied to
staged onodes, units allocated, extents written, csums made, the KV
batch built), over the ops the window completed. The syncs are not in
it: they run beside the loop."""
from benchmarks.layer_metrics import bstore_spans

NAME = "bstore_prepare_ms_per_op"
UNIT = "ms/op"
LAYER = "objectstore"
MOVES = "ops_s"


def read(ctx):
    txcs = bstore_spans.txcs(ctx)
    if not txcs or not ctx.ops:
        return None
    return sum(t["tags"]["prepare_us"] for t in txcs) / 1e3 / ctx.ops
