"""Median time a group commit spends writing its contexts' staged extents
into the block file, on the store's thread and ahead of the sync: the
`block_write_us` tag of the `bstore_kv_sync` spans that wrote a block
(`block_writes` > 0). It is a part of `block_sync_us`, so of
`bstore_sync_ms` and `bstore_commit_wait_ms`, and none of
`bstore_prepare_ms_per_op`: the writes left the caller's loop. 0.0
where the groups carry the tag and none wrote a block (every object fit
its onode); None where no group carries it (a program that writes its
extents while it prepares)."""
import statistics

from benchmarks.layer_metrics import bstore_spans

NAME = "bstore_block_write_ms"
UNIT = "ms"
LAYER = "objectstore"
MOVES = "op_p50_ms"


def read(ctx):
    groups = [g["tags"] for g in bstore_spans.groups(ctx)
              if "block_write_us" in g["tags"]]
    if not groups:
        return None
    wrote = [g["block_write_us"] for g in groups if g["block_writes"] > 0]
    return statistics.median(wrote) / 1e3 if wrote else 0.0
