"""Share of the dequeues that the daemons held back
(`osd_debug_inject_dispatch_delay_probability`): `dispatch_hold` spans
over the client ops the op queues handed to their PGs (`osd_op` spans)
plus the sub-reads their primaries sent (the sum of `shards_asked`),
inside the window. It reads the probability, in percent, when the
injector is sound; a program that holds nothing has no such span."""
NAME = "dispatch_held_pct"
UNIT = "%"
LAYER = "utils/work_queue+osd/scheduler"
MOVES = "op_p95_ms"


def read(ctx):
    held = ctx.spans.get("dispatch_hold", [])
    dequeues = len(ctx.spans.get("osd_op", [])) + sum(
        s["tags"].get("shards_asked", 0)
        for s in ctx.spans.get("ec_read", []))
    if not held or not dequeues:
        return None
    return 100.0 * len(held) / dequeues
