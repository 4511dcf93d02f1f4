"""Loop CPU an op in the self time of `scrub_round` and `scrub_chunk`: what
the scrub takes of the loop beside the reads, over the reads completed."""
from benchmarks.layer_metrics import loop_parts

NAME = "osd_scrub_ms_per_op"
UNIT = "ms/op"
LAYER = "osd/scrub"
MOVES = "ops_s"


def read(ctx):
    return loop_parts.ms_per_op(ctx, "osd.scrub")
