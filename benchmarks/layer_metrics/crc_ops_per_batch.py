"""Crc jobs per device batch: the offload service's `crc_jobs` over
`crc_batches` (device lanes only), deltas over the window. A job is one
scan chunk of one OSD; the members of a PG scan at once, so their jobs
can share a batch. No write's checksums are in it since PR 43 (an
encode's finisher makes them): only `ec_offload_crc_device` and scrub
make a crc job."""
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
