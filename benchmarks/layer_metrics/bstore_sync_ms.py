"""Median length of a group commit on a store's thread: the sync of the
block file and the KV's synced submit (the `bstore_kv_sync` span)."""
import statistics

from benchmarks.layer_metrics import bstore_spans

NAME = "bstore_sync_ms"
UNIT = "ms"
LAYER = "objectstore"
MOVES = "op_p50_ms"


def read(ctx):
    groups = bstore_spans.groups(ctx)
    if not groups:
        return None
    return statistics.median(g["duration_us"] for g in groups) / 1e3
