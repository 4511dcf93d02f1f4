"""Shared by the readers of the stores' two stages of the copy ledger
(`store_write`, `store_read` in `ceph_tpu/utils/copytrack.py`, which the
harness snapshots whole into `ctx.open["copy"]` / `ctx.close["copy"]`)."""


def direct_pct(ctx, stage):
    """Share of the stage's bytes that changed hands by reference, as
    deltas over the window. None on a program without the stage (a
    parent whose store copies every byte in and out and counts none) or
    where no such byte moved in the window."""
    before = ctx.open.get("copy", {}).get(stage)
    after = ctx.close.get("copy", {}).get(stage)
    if before is None or after is None:
        return None
    direct, copied = (after[k] - before[k]
                      for k in ("referenced_bytes", "copied_bytes"))
    if direct + copied <= 0:
        return None
    return 100.0 * direct / (direct + copied)
