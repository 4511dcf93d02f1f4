"""Decode dispatches of the window that found no codec for their
recovery matrix and built one: `tpu_decode_dispatch` spans tagged
`matrix_miss`. A miss is a GF(2^8) inversion, the expansion of the
matrix to bits and a `device_put`, once a pattern; a pool that meets new
patterns all through a window pays them inside it."""
NAME = "fastread_matrix_misses"
UNIT = "count"
LAYER = "ops/rs_codec kernel"
MOVES = "op_p95_ms"


def read(ctx):
    tagged = [s["tags"]["matrix_miss"]
              for s in ctx.spans.get("tpu_decode_dispatch", [])
              if "matrix_miss" in s["tags"]]
    if not tagged:
        return None
    return float(sum(bool(t) for t in tagged))
