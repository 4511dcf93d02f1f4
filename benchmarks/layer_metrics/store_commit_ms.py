"""Median `store_commit` span: one shard transaction into the store."""
import statistics

NAME = "store_commit_ms"
UNIT = "ms"
LAYER = "objectstore"
MOVES = "op_p50_ms"


def read(ctx):
    spans = ctx.spans.get("store_commit", [])
    if not spans:
        return None
    return statistics.median(s["duration_us"] for s in spans) / 1e3
