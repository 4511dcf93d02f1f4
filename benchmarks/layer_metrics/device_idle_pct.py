"""1 - (union of the device's operation intervals / window), from the
profiler's trace."""
NAME = "device_idle_pct"
UNIT = "%"
LAYER = "device"
MOVES = "ops_s"


def read(ctx):
    if ctx.trace is None:
        return None
    return 100.0 * (1.0 - ctx.trace["busy_s"] / ctx.trace["window_s"])
