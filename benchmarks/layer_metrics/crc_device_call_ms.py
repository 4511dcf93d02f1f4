"""As `offload_device_call_ms`, over the `offload_batch` spans of kind
`crc` on a device lane: the median of the staged dispatch itself
(`h2d_submit_us`, `launch_us`, `result_wait_us`: H2D of the padded
blocks, the kernel, D2H of four bytes a block). Scrub's batches alone
since PR 43: a write's checksums are no crc job any more."""
from benchmarks.layer_metrics import crc_batches

NAME = "crc_device_call_ms"
UNIT = "ms"
LAYER = "H2D/D2H link"
MOVES = "op_p50_ms"
HOPS = ("h2d_submit_us", "launch_us", "result_wait_us")


def read(ctx):
    return crc_batches.median_ms(ctx, HOPS)
