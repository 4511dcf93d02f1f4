"""Median `offload_queue_wait` span of an encode's rider: from its
admission to the service until its batch has the slot and is staged,
so the linger, a turn of the loop for the flush task and the slot's
semaphore. The span carries its batch's `kind` (a program that does not
tag it gives nothing to read)."""
import statistics

NAME = "enc_queue_wait_ms"
UNIT = "ms"
LAYER = "offload/service"
MOVES = "op_p50_ms"


def read(ctx):
    waits = [s["duration_us"] for s in ctx.spans.get("offload_queue_wait", [])
             if s["tags"].get("kind") == "enc"]
    return statistics.median(waits) / 1e3 if waits else None
