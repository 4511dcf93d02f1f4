"""Median `ec_recover` span: from the primary stacking the k gathered
chunks of an object until the lost shard's bytes are back from the
device (batcher wait, H2D, kernel, D2H), per shard rebuilt. The gather
before it and the push after it are not in it."""
import statistics

from benchmarks.layer_metrics import recovery_spans

NAME = "ec_recover_ms"
UNIT = "ms"
LAYER = "osd/pg+osd/ec_backend"
MOVES = "op_p50_ms"


def read(ctx):
    spans = recovery_spans.recovers(ctx)
    if not spans:
        return None
    return statistics.median(s["duration_us"] for s in spans) / 1e3
