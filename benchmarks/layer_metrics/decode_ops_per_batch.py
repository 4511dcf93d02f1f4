"""Decode jobs per device batch: the offload service's `dec_jobs` over
`dec_batches`, deltas over the window. Decode buckets are keyed by
erasure pattern, so only reads that lost the same chunk and gathered the
same survivors can share a batch."""
NAME = "decode_ops_per_batch"
UNIT = "ops/batch"
LAYER = "offload/service"
MOVES = "ops_s"


def read(ctx):
    before, after = ctx.open.get("offload", {}), ctx.close.get("offload", {})
    if any(k not in d for d in (before, after)
           for k in ("dec_jobs", "dec_batches")):
        return None
    batches = after["dec_batches"] - before["dec_batches"]
    if not batches:
        return None
    return (after["dec_jobs"] - before["dec_jobs"]) / batches
