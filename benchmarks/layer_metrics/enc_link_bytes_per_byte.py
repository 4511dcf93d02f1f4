"""Bytes over the host-device link, both ways (`copytrack` h2d + d2h),
per user byte of the encodes that ended inside the window, where the
encodes had the link to themselves: the data chunks up once and the
parity down once, `benchmarks/reference_small.link_bytes_per_user_byte`
(1.375 for whole stripes at k=8 m=3).

The user bytes are the service's own count of what those batches
encoded (`enc_bytes`: whole stripes) less the padding the reference
gives an object of the configuration's size, so the quotient has no
edge: `link_bytes_per_byte` divides by the ops that completed in the
window, which are not quite the batches that did. A window in which a
decode or a device crc batch also ended has no link bytes that are the
encodes' alone: nothing to read there."""
from benchmarks import reference_small
from benchmarks.layer_metrics import enc_batches

NAME = "enc_link_bytes_per_byte"
UNIT = "B/B"
LAYER = "H2D/D2H link"
MOVES = "ops_s"


def read(ctx):
    d = enc_batches.deltas(ctx)
    before, after = ctx.open.get("copy", {}), ctx.close.get("copy", {})
    if d is None or not d["enc_bytes"] or not enc_batches.alone(ctx) \
            or any(stage not in snap for snap in (before, after)
                   for stage in ("h2d", "d2h")):
        return None
    pool = ctx.cell.config["pool"]
    size = ctx.cell.config["object_size"]
    padded = reference_small.layout(size, pool["k"], pool["m"],
                                    pool["stripe_unit"])["padded_bytes"]
    moved = sum(after[s]["copied_bytes"] - before[s]["copied_bytes"]
                for s in ("h2d", "d2h"))
    return moved / (d["enc_bytes"] * size / padded) if moved else None
