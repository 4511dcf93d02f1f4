"""The share of the window's encode batches that the linger's deadline
shipped and not a full bucket: the `flush` tag of the `offload_batch`
spans of kind `enc` (`linger` | `full`; the service counts the same in
`stats` as `flush_linger` / `flush_full`, over every kind). At 100 the
batcher never filled `max_batch_bytes` and every op paid the linger."""
from benchmarks.layer_metrics import enc_batches

NAME = "enc_linger_flush_pct"
UNIT = "%"
LAYER = "offload/service"
MOVES = "op_p50_ms"


def read(ctx):
    rules = [t["flush"] for t in enc_batches.tags(ctx) if "flush" in t]
    if not rules:
        return None
    return 100.0 * rules.count("linger") / len(rules)
