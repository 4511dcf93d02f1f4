"""Share of the bytes the stores staged for their block files that were
acknowledged from the KV's sync alone (deferred): `deferred_bytes` over
`bytes` of the window's `bstore_txc` spans. 100 where every data write
was under the line (an 8 KiB shard is), 0.0 where none was; None where
the program has no such path or the window staged no data."""
from benchmarks.layer_metrics import deferred_spans

NAME = "bstore_deferred_bytes_pct"
UNIT = "%"
LAYER = "objectstore"
MOVES = "op_p50_ms"


def read(ctx):
    txcs = deferred_spans.txcs(ctx)
    staged = sum(t["bytes"] for t in txcs)
    if not staged:
        return None
    return 100.0 * sum(t["deferred_bytes"] for t in txcs) / staged
