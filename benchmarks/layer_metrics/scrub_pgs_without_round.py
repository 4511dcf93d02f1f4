"""PGs of the cell's pool (`pool.pg_num` of the configuration) that no
`scrub_round` span of `state` done names in the window: those whose
turn did not come. None where no round ran to its end."""
from benchmarks.layer_metrics import scrub_spans

NAME = "scrub_pgs_without_round"
UNIT = "pgs"
LAYER = "osd/scrub"
MOVES = "ops_s"


def read(ctx):
    done = scrub_spans.rounds(ctx, "done")
    if not done:
        return None
    seen = {s["tags"].get("pgid") for s in done}
    return float(ctx.cell.config["pool"]["pg_num"] - len(seen))
