"""Transaction contexts a group commit covers, mean over the window's
groups on all stores (`bstore_kv_sync.txcs`): what one sync of the block
file and one of the KV log are shared by. 1 is a store that syncs for
every transaction, as the parent's did."""
from benchmarks.layer_metrics import bstore_spans

NAME = "bstore_txcs_per_sync"
UNIT = "txcs/sync"
LAYER = "objectstore"
MOVES = "ops_s"


def read(ctx):
    groups = bstore_spans.groups(ctx)
    if not groups:
        return None
    return sum(g["tags"]["txcs"] for g in groups) / len(groups)
