"""Median per `offload_batch` of the staged dispatch itself, on the
staging-pool thread: `h2d_submit_us` (device_put returns), `launch_us`
(the kernel call returns) and `result_wait_us` (np.asarray returns:
kernel and D2H)."""
import statistics

NAME = "offload_device_call_ms"
UNIT = "ms"
LAYER = "H2D/D2H link"
MOVES = "op_p50_ms"
HOPS = ("h2d_submit_us", "launch_us", "result_wait_us")


def read(ctx):
    spans = [s["tags"] for s in ctx.spans.get("offload_batch", [])
             if all(h in s["tags"] for h in HOPS)]
    if not spans:
        return None
    return statistics.median(sum(t[h] for h in HOPS) for t in spans) / 1e3
