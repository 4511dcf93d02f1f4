"""Share of the window's rounds that were given up at the reservation
(`state` reserve_failed: a member had no free slot, or did not answer),
of all rounds begun. Each costs its primary a few small messages and
the PG a turn."""
from benchmarks.layer_metrics import scrub_spans

NAME = "scrub_reserve_failed_pct"
UNIT = "%"
LAYER = "osd/scrub"
MOVES = "ops_s"


def read(ctx):
    rounds = scrub_spans.rounds(ctx)
    if not rounds:
        return None
    return 100.0 * len(scrub_spans.rounds(ctx, "reserve_failed")) \
        / len(rounds)
