"""Encode jobs (one a `write_full`) per device batch: the offload
service's `enc_jobs` over `enc_batches`, deltas over the window. Unlike
`offload_ops_per_batch` it counts the encodes alone, whatever else the
device lanes served."""
from benchmarks.layer_metrics import enc_batches

NAME = "enc_ops_per_batch"
UNIT = "ops/batch"
LAYER = "offload/service"
MOVES = "ops_s"


def read(ctx):
    d = enc_batches.deltas(ctx)
    return None if d is None else d["enc_jobs"] / d["enc_batches"]
