"""Median time a transaction waits between the return of
`queue_transaction` and its `on_commit`: `queued_us` (for the commit
thread to take it), the group's `block_sync_us` and `kv_submit_us`, and
`deliver_us` (from the thread back to the loop). An op waits for eleven
shards' and their log entries'."""
import statistics

from benchmarks.layer_metrics import bstore_spans

NAME = "bstore_commit_wait_ms"
UNIT = "ms"
LAYER = "objectstore"
MOVES = "op_p50_ms"
LEGS = ("queued_us", "block_sync_us", "kv_submit_us", "deliver_us")


def read(ctx):
    txcs = bstore_spans.txcs(ctx)
    if not txcs:
        return None
    return statistics.median(sum(t["tags"][k] for k in LEGS)
                             for t in txcs) / 1e3
