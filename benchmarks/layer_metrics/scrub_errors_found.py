"""What the window's scrub rounds found: the sum of the `errors` their
`scrub_round` spans carry (inconsistent shards or copies the compare
reported, whether repaired or not). On a healthy pool it is 0, and
anything else is a digest that differs from the stored one: a rotted
shard, or a digest computed wrongly. None where no round ran to its
end, or where the spans carry no such tag."""
from benchmarks.layer_metrics import scrub_spans

NAME = "scrub_errors_found"
UNIT = "errors"
LAYER = "osd/scrub"
MOVES = "ops_s"


def read(ctx):
    done = [s["tags"] for s in scrub_spans.rounds(ctx, "done")]
    if not done or any("errors" not in t for t in done):
        return None
    return float(sum(t["errors"] for t in done))
