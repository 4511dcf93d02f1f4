"""Median per `offload_batch` of the three waits between its four hops:
`sem_wait_us` (the slot semaphore), `pool_wait_us` (until the
staging-pool thread starts) and `resume_us` (until the coroutine runs
again on the loop). Nothing computes in them. Since PR 43 the pool's
thread also makes a batch's stacking copy and its riders' finishers,
so `pool_wait_us` grows with a busier lane: a rise here beside a fall
of `loop_offload_pct` is work that left the loop, not a slower hop."""
import statistics

NAME = "offload_handoff_ms"
UNIT = "ms"
LAYER = "offload/service"
MOVES = "op_p50_ms"
HOPS = ("sem_wait_us", "pool_wait_us", "resume_us")


def read(ctx):
    spans = [s["tags"] for s in ctx.spans.get("offload_batch", [])
             if all(h in s["tags"] for h in HOPS)]
    if not spans:
        return None
    return statistics.median(sum(t[h] for h in HOPS) for t in spans) / 1e3
