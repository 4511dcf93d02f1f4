"""The shard reads a fast read bought and did not use: the sum of `late`
(replies it did not wait for) over the sum of `shards_asked`, over the
window's fast reads. At k=8 m=3 with every shard alive a read asks 10
and answers from 7 of them and its own chunk: 30."""
from benchmarks.layer_metrics import fastread_spans

NAME = "fastread_late_replies_pct"
UNIT = "%"
LAYER = "osd/ec_backend"
MOVES = "ops_s"


def read(ctx):
    reads = [t for t in fastread_spans.fast_reads(ctx)
             if "late" in t and "shards_asked" in t]
    asked = sum(t["shards_asked"] for t in reads)
    if not asked:
        return None
    return 100.0 * sum(t["late"] for t in reads) / asked
