"""As `offload_handoff_ms`, over the `offload_batch` spans of kind
`enc`: the median of the three waits in which nothing computes
(`sem_wait_us`, `pool_wait_us`, `resume_us`)."""
from benchmarks.layer_metrics import enc_batches

NAME = "enc_handoff_ms"
UNIT = "ms"
LAYER = "offload/service"
MOVES = "op_p50_ms"
HOPS = ("sem_wait_us", "pool_wait_us", "resume_us")


def read(ctx):
    return enc_batches.median_ms(ctx, HOPS)
