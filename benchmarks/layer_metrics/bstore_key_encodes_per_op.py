"""The JSON key encodings the stores made for a client op: the
`key_encodes` tags of the window's transaction contexts, each the
encodings its store made since its previous context was queued (so the
`exists` probes in front of a transaction are in it), over the ops the
window completed. A collection's and an object's key are pure functions
of their ids; a store that remembers them encodes an id once, a new
object and its rollback generation a shard. None where no context
carries the tag (a program that encodes a key wherever it needs one
and does not count)."""
from benchmarks.layer_metrics import bstore_spans

NAME = "bstore_key_encodes_per_op"
UNIT = "count/op"
LAYER = "objectstore"
MOVES = "ops_s"


def read(ctx):
    counts = [t["tags"]["key_encodes"] for t in bstore_spans.txcs(ctx)
              if "key_encodes" in t["tags"]]
    if not counts or not ctx.ops:
        return None
    return sum(counts) / ctx.ops
