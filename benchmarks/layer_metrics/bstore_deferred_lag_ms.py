"""How long an acknowledged deferred write waits for the block file:
the median over the window's `bstore_deferred_flush` spans of
`median_lag_us`, from a rider's acknowledgement (its group's KV sync)
to the end of the batch's `fdatasync`. For that long the write lives in
the KV's log and in memory alone."""
import statistics

from benchmarks.layer_metrics import deferred_spans

NAME = "bstore_deferred_lag_ms"
UNIT = "ms"
LAYER = "objectstore"
MOVES = "op_p95_ms"


def read(ctx):
    lags = [f["median_lag_us"] for f in deferred_spans.flushes(ctx)
            if "median_lag_us" in f]
    return statistics.median(lags) / 1e3 if lags else None
