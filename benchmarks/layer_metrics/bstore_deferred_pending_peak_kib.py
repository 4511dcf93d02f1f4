"""The most a store held staged for a deferred write and not landed:
over the window's `bstore_deferred_flush` spans, the batch's `bytes`
and the `pending_bytes` left after it, in KiB. `prepare` waits where it
would pass the store's bound."""
from benchmarks.layer_metrics import deferred_spans

NAME = "bstore_deferred_pending_peak_kib"
UNIT = "KiB"
LAYER = "objectstore"
MOVES = "op_p95_ms"


def read(ctx):
    flushes = [f for f in deferred_spans.flushes(ctx)
               if "pending_bytes" in f]
    if not flushes:
        return None
    return max(f["bytes"] + f["pending_bytes"] for f in flushes) / 1024
