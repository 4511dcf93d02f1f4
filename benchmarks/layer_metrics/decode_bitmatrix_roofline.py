"""The codec program's share of its roofline while it reconstructs: the
least time the chip could take for the window's decode batches, each at
its true r (the chunks it had to rebuild), over the device time the
trace gives the program that ran them.

A batch is reckoned as `apply_bitmatrix_batched_roofline` reckons an
encode (`least_seconds`: bytes b(k+r)n, ops 2(8r)(8k)bn, `peaks.json`),
from the `bytes` (unpadded input, b*k*n) and `r` tags of its
`offload_batch` span. The tpu plugin pads the recovery matrix to m rows
so that a decode runs the encode program of its shape; that, like the
padding of b to a power of two, shows as a lower share, as it should.
One program serves both directions, so a window that also encodes has
no device time that is the decodes' alone: nothing to read there."""
from benchmarks.layer_metrics import decode_batches
from benchmarks.layer_metrics.apply_bitmatrix_batched_roofline import (
    PROGRAM, least_seconds)

NAME = "decode_bitmatrix_roofline"
UNIT = "%"
LAYER = "ops/rs_codec kernel"
MOVES = "ops_s"


def read(ctx):
    if ctx.trace is None or ctx.peaks is None:
        return None
    batches = [t for t in decode_batches.tags(ctx)
               if "r" in t and "bytes" in t]
    kernel_s = ctx.trace["programs"].get(PROGRAM, 0.0)
    encodes = any(s["tags"].get("kind") == "enc"
                  for s in ctx.spans.get("offload_batch", []))
    if not batches or not kernel_s or encodes:
        return None
    k = ctx.cell.config["pool"]["k"]
    least = sum(max(least_seconds(t["bytes"], k, t["r"], ctx.peaks).values())
                for t in batches)
    return 100.0 * least / kernel_s
