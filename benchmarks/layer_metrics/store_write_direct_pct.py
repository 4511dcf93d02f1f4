"""Share of the payload bytes transactions wrote that the stores kept as
the buffers they arrived in (`store_write` in the copy ledger:
referenced over referenced + copied, deltas over the window). Copied is
what `Transaction.write` snapshotted out of a mutable buffer (the
primary's own shard, a plane of the encode's output: it is then kept
without a second copy, and counts on both sides), what went into an
object the store had made private, and a view too small a part of the
buffer under it to be kept."""
from benchmarks.layer_metrics.store_direct import direct_pct

NAME = "store_write_direct_pct"
UNIT = "%"
LAYER = "objectstore"
MOVES = "op_p50_ms"


def read(ctx):
    return direct_pct(ctx, "store_write")
