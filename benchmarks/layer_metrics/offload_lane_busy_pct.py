"""`device_stats[..].busy_s` over the window. This is host wall time
around a staged dispatch (stack, H2D, kernel, D2H) from a thread that
shares the GIL with the loop: the lane's occupancy, never device busy
time, which `device_idle_pct` reads from the trace."""
NAME = "offload_lane_busy_pct"
UNIT = "%"
LAYER = "offload/service"
MOVES = "ops_s"


def read(ctx):
    if not ctx.device_delta("batches"):
        return None
    return 100.0 * ctx.device_delta("busy_s") / ctx.window_s
