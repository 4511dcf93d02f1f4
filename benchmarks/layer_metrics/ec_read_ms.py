"""Median `ec_read` span: the primary gathering k chunks from its shards
(`_gather_chunks`) and interleaving them, per client read."""
import statistics

NAME = "ec_read_ms"
UNIT = "ms"
LAYER = "osd/ec_backend"
MOVES = "op_p50_ms"


def read(ctx):
    spans = ctx.spans.get("ec_read", [])
    if not spans:
        return None
    return statistics.median(s["duration_us"] for s in spans) / 1e3
