"""How many different erasure patterns the offload service met in the
window: the distinct `pattern` tags ("survivors>wanted") of its `dec`
batches. The service buckets decode jobs by pattern, so two reads share
a batch only where the same chunks came first for both; a degraded pool
has one pattern a PG, a fast read draws one from the stragglers of the
moment (up to 175 a primary position at k=8 m=3)."""
from benchmarks.layer_metrics import decode_batches

NAME = "fastread_decode_patterns"
UNIT = "count"
LAYER = "offload/service"
MOVES = "ops_s"


def read(ctx):
    patterns = {t["pattern"] for t in decode_batches.tags(ctx)
                if "pattern" in t}
    return float(len(patterns)) if patterns else None
