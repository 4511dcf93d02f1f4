"""Encode jobs per device batch: the offload service's per-device
`ops` over `batches`, device lanes only (the host lane's checksum jobs
are left out)."""
NAME = "offload_ops_per_batch"
UNIT = "ops/batch"
LAYER = "offload/service"
MOVES = "ops_s"


def read(ctx):
    batches = ctx.device_delta("batches")
    if not batches:
        return None
    return ctx.device_delta("ops") / batches
