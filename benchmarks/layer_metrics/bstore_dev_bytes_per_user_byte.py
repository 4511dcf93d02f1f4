"""Bytes the stores wrote to their files for each byte the clients
wrote: every group's `block_bytes` (extents, padded to 4 KiB) and
`kv_bytes` (the log's records, the freelist value among them, and the
runs a flush or a compaction wrote), over the window's user bytes.
(k+m)/k = 1.375 is the code's floor."""
from benchmarks.layer_metrics import bstore_spans

NAME = "bstore_dev_bytes_per_user_byte"
UNIT = "B/B"
LAYER = "objectstore"
MOVES = "ops_s"


def read(ctx):
    groups = bstore_spans.groups(ctx)
    written = ctx.user_bytes.get("write", 0)
    if not groups or not written:
        return None
    return sum(g["tags"]["block_bytes"] + g["tags"]["kv_bytes"]
               for g in groups) / written
