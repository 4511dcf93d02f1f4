"""The codec program's share of its roofline while it encodes small
batches: the least time the chip could take for the window's encode
bytes (`enc_bytes` of `svc.stats`: unpadded input, b*k*n), reckoned by
`apply_bitmatrix_batched_roofline.least_seconds` (imported: the
functions that count the kernel's bytes and operations stay one), over
the device time the trace gives the program that ran them.

The codec pads a batch's rows to a power of two (2 to 32 here, where a
4 MiB cell stages 128 or 256): counted at the unpadded bytes, that
shows as a lower share, as it should, and a reading over 100 is a fault
of the count. One program serves encodes and decodes, so a window in
which a decode batch also ended has no device time that is the
encodes' alone: nothing to read there."""
from benchmarks.layer_metrics import enc_batches
from benchmarks.layer_metrics.apply_bitmatrix_batched_roofline import (
    PROGRAM, least_seconds)

NAME = "enc_bitmatrix_roofline"
UNIT = "%"
LAYER = "ops/rs_codec kernel"
MOVES = "ops_s"


def read(ctx):
    if ctx.trace is None or ctx.peaks is None:
        return None
    d = enc_batches.deltas(ctx)
    kernel_s = ctx.trace["programs"].get(PROGRAM, 0.0)
    if d is None or not d["enc_bytes"] or not kernel_s \
            or not enc_batches.alone(ctx):
        return None
    pool = ctx.cell.config["pool"]
    least = least_seconds(d["enc_bytes"], pool["k"], pool["m"], ctx.peaks)
    return 100.0 * max(least.values()) / kernel_s
