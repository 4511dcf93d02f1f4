"""As `offload_handoff_ms`, over the `offload_batch` spans of kind
`dec`: the median of the three waits in which nothing computes
(`sem_wait_us`, `pool_wait_us`, `resume_us`)."""
from benchmarks.layer_metrics import decode_batches

NAME = "decode_handoff_ms"
UNIT = "ms"
LAYER = "offload/service"
MOVES = "op_p50_ms"
HOPS = ("sem_wait_us", "pool_wait_us", "resume_us")


def read(ctx):
    return decode_batches.median_ms(ctx, HOPS)
