"""Median `scrub_round` span among the rounds that ran to the end
(`state` done): reservations, QoS grants, the scan of every member, the
digests, the compare. While it lasts the PG's writes meet a gate, range
by range, and its members' slots are taken."""
import statistics

from benchmarks.layer_metrics import scrub_spans

NAME = "scrub_round_ms"
UNIT = "ms"
LAYER = "osd/scrub"
MOVES = "op_p95_ms"


def read(ctx):
    done = scrub_spans.rounds(ctx, "done")
    if not done:
        return None
    return statistics.median(s["duration_us"] for s in done) / 1e3
