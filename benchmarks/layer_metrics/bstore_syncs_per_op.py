"""`fsync`s and `fdatasync`s a client op costs, on all stores: each
group's sync of the block file (`block_synced`) and the KV's syncs
inside its submit (`kv_fsyncs`: the log's, and a memtable flush's or a
compaction's where one fell due), over the ops the window completed.
The parent's store synced twice a transaction, 22 times an op and
more."""
from benchmarks.layer_metrics import bstore_spans

NAME = "bstore_syncs_per_op"
UNIT = "fsyncs/op"
LAYER = "objectstore"
MOVES = "op_p50_ms"


def read(ctx):
    groups = bstore_spans.groups(ctx)
    if not groups or not ctx.ops:
        return None
    return sum(g["tags"]["block_synced"] + g["tags"]["kv_fsyncs"]
               for g in groups) / ctx.ops
