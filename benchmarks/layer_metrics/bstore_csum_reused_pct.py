"""Share of the bytes the stores staged for their block files whose
per-block checksums came with the write (`Transaction.write`'s `csums`:
an EC shard's per-chunk crc32c, taken beside the encode) and were not
computed again in `prepare`: the `csum_reused_bytes` tags over the
`bytes` tags of the window's transaction contexts. A read verifies
either kind the same. 0.0 where the contexts carry the tag and no write
brought checksums that fit, or nothing was staged at all (every object
fit its onode); None where no context carries it (a program whose
stores always compute)."""
from benchmarks.layer_metrics import bstore_spans

NAME = "bstore_csum_reused_pct"
UNIT = "%"
LAYER = "objectstore"
MOVES = "ops_s"


def read(ctx):
    tags = [t["tags"] for t in bstore_spans.txcs(ctx)
            if "csum_reused_bytes" in t["tags"]]
    if not tags:
        return None
    staged = sum(t["bytes"] for t in tags)
    reused = sum(t["csum_reused_bytes"] for t in tags)
    return 100.0 * reused / staged if staged else 0.0
