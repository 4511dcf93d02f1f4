"""CPU time of the event-loop thread (every daemon and the client run
on it) over the window. Near 100 the host's Python is the bottleneck."""
NAME = "loop_busy_pct"
UNIT = "%"
LAYER = "event loop (all daemons)"
MOVES = "ops_s"


def read(ctx):
    return 100.0 * (ctx.close["loop_cpu_s"] - ctx.open["loop_cpu_s"]) \
        / ctx.window_s
