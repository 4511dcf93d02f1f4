"""What the readers of BlueStore's deferred path share
(`ceph_tpu/objectstore/bluestore.py`, `ceph_tpu/kv/lsm.py`). A write
under `bluestore_prefer_deferred_size` rides its transaction's KV batch
and is acknowledged from the KV's sync alone: its `bstore_txc` span
says how many of its staged `bytes` were `deferred_bytes`, and its
group's `bstore_kv_sync` how many records it carried (`deferred_in`)
and removed (`deferred_removed`). `bstore_deferred_flush` is one batch
landing on a store's block file, on its commit thread: `ops` extents
and `bytes` written in `write_us`, one `fdatasync` of `sync_us`,
`median_lag_us` and `oldest_lag_us` from its riders' acknowledgements
to that sync's end, `pending_bytes` still staged after it. `kv_flush`
and `kv_compact` are the KV's memtable flushes and compactions, from
the thread that ran them: `bytes_in`, `bytes_out`, `entries`,
`dropped`. A program without the path records none of these tags, and
every reader returns None."""


def txcs(ctx):
    return [s["tags"] for s in ctx.spans.get("bstore_txc", [])
            if "deferred_bytes" in s["tags"]]


def groups(ctx):
    return [s["tags"] for s in ctx.spans.get("bstore_kv_sync", [])
            if "deferred_in" in s["tags"]]


def flushes(ctx):
    return [s["tags"] for s in ctx.spans.get("bstore_deferred_flush", [])
            if "ops" in s["tags"]]


def maintenance(ctx):
    """The KV's flushes and compactions in the window, whole spans."""
    return [s for name in ("kv_flush", "kv_compact")
            for s in ctx.spans.get(name, []) if "bytes_out" in s["tags"]]
