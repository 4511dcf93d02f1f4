"""Bytes over the host-device link, both ways (`copytrack` h2d + d2h),
per byte the clients read inside the window. A reconstructing read
sends its k surviving chunks up and brings back the rows the program
computed; a healthy read moves nothing."""
NAME = "decode_link_bytes_per_byte"
UNIT = "B/B"
LAYER = "H2D/D2H link"
MOVES = "ops_s"


def read(ctx):
    read_bytes = ctx.user_bytes.get("read", 0)
    before, after = ctx.open.get("copy", {}), ctx.close.get("copy", {})
    if not read_bytes or any(s not in d for d in (before, after)
                             for s in ("h2d", "d2h")):
        return None
    moved = sum(after[s]["copied_bytes"] - before[s]["copied_bytes"]
                for s in ("h2d", "d2h"))
    return moved / read_bytes if moved else None
