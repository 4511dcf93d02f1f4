"""Share of the OSDs' op time spent waiting in the op queue: the
`queue_wait_us` tag of each `osd_op` span over the span's duration."""
NAME = "queue_wait_pct"
UNIT = "%"
LAYER = "utils/work_queue+osd/scheduler"
MOVES = "op_p95_ms"


def read(ctx):
    spans = ctx.spans.get("osd_op", [])
    total = sum(s["duration_us"] for s in spans)
    if not total:
        return None
    return 100.0 * sum(s["tags"].get("queue_wait_us", 0.0)
                       for s in spans) / total
