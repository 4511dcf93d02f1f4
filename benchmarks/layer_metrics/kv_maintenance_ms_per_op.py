"""Time the stores' commit threads spend in the KV's upkeep for each
client op: the lengths of the window's `kv_flush` and `kv_compact`
spans over the ops it completed. It runs behind a group's
acknowledgements and ahead of the next group's, and the interpreter is
held against the loop for its Python part. 0.0 where the program has
the path and nothing fell due; None where it has not."""
from benchmarks.layer_metrics import deferred_spans

NAME = "kv_maintenance_ms_per_op"
UNIT = "ms/op"
LAYER = "objectstore"
MOVES = "op_p95_ms"


def read(ctx):
    if not deferred_spans.groups(ctx) or not ctx.ops:
        return None
    return sum(s["duration_us"]
               for s in deferred_spans.maintenance(ctx)) / 1e3 / ctx.ops
