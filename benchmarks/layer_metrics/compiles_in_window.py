"""`jax.monitoring` backend-compile events (a compile or a load from
the persistent cache) that ended inside the window. Should be 0."""
NAME = "compiles_in_window"
UNIT = "count"
LAYER = "device"
MOVES = "op_p95_ms"


def read(ctx):
    return float(ctx.compiles_in_window)
