"""Mean number of chunks a reconstructing fast read rebuilt: the length
of the `missing` tag over the window's `ec_decode` spans. By the
arithmetic of one reply in ten held back, 1.3 (r = 1: 71% of the
decoding reads, 2: 24%, 3: 4%)."""
from benchmarks.layer_metrics import fastread_spans

NAME = "fastread_mean_r"
UNIT = "chunks"
LAYER = "osd/ec_backend"
MOVES = "ops_s"


def read(ctx):
    missing = [len(s["tags"]["missing"])
               for s in ctx.spans.get("ec_decode", [])
               if "missing" in s["tags"]]
    if not fastread_spans.fast_reads(ctx) or not missing:
        return None
    return sum(missing) / len(missing)
