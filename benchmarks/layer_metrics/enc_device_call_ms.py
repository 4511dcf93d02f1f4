"""As `offload_device_call_ms`, over the `offload_batch` spans of kind
`enc`: the median of the staged dispatch itself (`h2d_submit_us`,
`launch_us`, `result_wait_us`: H2D of the data chunks, the kernel, D2H
of the parity)."""
from benchmarks.layer_metrics import enc_batches

NAME = "enc_device_call_ms"
UNIT = "ms"
LAYER = "H2D/D2H link"
MOVES = "op_p50_ms"
HOPS = ("h2d_submit_us", "launch_us", "result_wait_us")


def read(ctx):
    return enc_batches.median_ms(ctx, HOPS)
