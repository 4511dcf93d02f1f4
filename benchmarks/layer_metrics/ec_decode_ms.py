"""Median `ec_decode` span: from the primary stacking the k surviving
chunks until the object's bytes are assembled (stacking copy, batcher
wait, H2D, kernel, D2H, interleave and `tobytes`), per reconstructing
read."""
import statistics

NAME = "ec_decode_ms"
UNIT = "ms"
LAYER = "osd/ec_backend"
MOVES = "op_p50_ms"


def read(ctx):
    spans = ctx.spans.get("ec_decode", [])
    if not spans:
        return None
    return statistics.median(s["duration_us"] for s in spans) / 1e3
