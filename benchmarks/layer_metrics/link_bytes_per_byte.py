"""Bytes over the host-device link, both ways (`copytrack` h2d + d2h),
per byte the clients wrote inside the window."""
NAME = "link_bytes_per_byte"
UNIT = "B/B"
LAYER = "H2D/D2H link"
MOVES = "ops_s"


def read(ctx):
    written = ctx.user_bytes["write"]
    if not written:
        return None
    moved = sum(ctx.close["copy"][s]["copied_bytes"]
                - ctx.open["copy"][s]["copied_bytes"]
                for s in ("h2d", "d2h"))
    return moved / written
