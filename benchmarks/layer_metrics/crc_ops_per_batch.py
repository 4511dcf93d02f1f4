"""Crc jobs per device batch: the offload service's `crc_jobs` over
`crc_batches` (device lanes only), deltas over the window. A job is one
scan chunk of one OSD (a write's checksums too, in a cell that writes);
the members of a PG scan at once, so their jobs can share a batch."""
NAME = "crc_ops_per_batch"
UNIT = "jobs/batch"
LAYER = "offload/service"
MOVES = "ops_s"


def read(ctx):
    before, after = ctx.open.get("offload", {}), ctx.close.get("offload", {})
    if any(k not in d for d in (before, after)
           for k in ("crc_jobs", "crc_batches")):
        return None
    batches = after["crc_batches"] - before["crc_batches"]
    if not batches:
        return None
    return (after["crc_jobs"] - before["crc_jobs"]) / batches
