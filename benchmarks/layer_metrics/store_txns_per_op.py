"""The store transactions a client op costs, on all OSDs: the window's
`store_commit` spans (one a `queue_transaction`, on any store) over the
ops the window completed. An 8+3 `write_full` is eleven shard writes
and the primary's log intent; a replica that queues its PG-log entry
as a transaction of its own, after the shard's, makes it 22 and more,
one whose entry rides the shard's transaction 12. It is the count of
how often that engages. Whatever else the window queues (a recovery's
pushes, peering's bookkeeping, a rollback generation's clone) is in it.
None where no span is there or no op completed."""

NAME = "store_txns_per_op"
UNIT = "txns/op"
LAYER = "objectstore"
MOVES = "ops_s"


def read(ctx):
    spans = ctx.spans.get("store_commit", [])
    if not spans or not ctx.ops:
        return None
    return len(spans) / ctx.ops
