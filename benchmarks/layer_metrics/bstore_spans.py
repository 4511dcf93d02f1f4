"""What the `bstore_*` readers share: the two spans of BlueStore's commit
pipeline (`ceph_tpu/objectstore/bluestore.py`). `bstore_txc` is one
transaction context from the start of its prepare to the delivery of
its callbacks, its legs as tags in microseconds (`prepare_us` on the
caller's loop, `queued_us` waiting for the commit thread,
`block_sync_us` and `kv_submit_us` of the group that covered it,
`deliver_us` from the thread back to the loop), with `ops`, `bytes`,
`group` and `ran_ahead`. `bstore_kv_sync` is one group commit on a
store's thread: `txcs`, `block_synced` (0 or 1), `kv_fsyncs`,
`block_bytes`, `kv_bytes`, `freelist_bytes`. A program without the
pipeline records neither, and every reader returns None."""


def txcs(ctx):
    return [s for s in ctx.spans.get("bstore_txc", [])
            if "prepare_us" in s["tags"]]


def groups(ctx):
    return [s for s in ctx.spans.get("bstore_kv_sync", [])
            if "txcs" in s["tags"]]
