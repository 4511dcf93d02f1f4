"""Median `ec_encode` span: from the primary handing stripes to the
codec until parity is back (batcher wait, H2D, kernel, D2H)."""
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
