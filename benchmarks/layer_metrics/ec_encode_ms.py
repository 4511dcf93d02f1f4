"""Median `ec_encode` span: from the primary handing stripes to the
codec until the shards are back (batcher wait, stacking copy, H2D,
kernel, D2H, the shard planes' assembly). Since PR 43 it also holds
each shard's crc32c by block, made in the batch's finisher on the
staging-pool thread: the crc job that used to follow the span is gone,
so the span is longer where the op is not."""
import statistics

NAME = "ec_encode_ms"
UNIT = "ms"
LAYER = "osd/ec_backend"
MOVES = "op_p50_ms"


def read(ctx):
    spans = ctx.spans.get("ec_encode", [])
    if not spans:
        return None
    return statistics.median(s["duration_us"] for s in spans) / 1e3
