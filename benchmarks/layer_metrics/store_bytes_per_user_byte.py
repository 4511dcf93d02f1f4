"""Bytes the OSDs' stores hold (`used_bytes`, after the window) per
byte of live user data, as the reference counts it."""
NAME = "store_bytes_per_user_byte"
UNIT = "B/B"
LAYER = "objectstore"
MOVES = "ops_s"


def read(ctx):
    if not ctx.live_user_bytes:
        return None
    return ctx.store_bytes / ctx.live_user_bytes
