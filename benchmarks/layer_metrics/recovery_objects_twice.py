"""Shards rebuilt more than once for one target within one interval:
`ec_recover` spans less the distinct (`oid`, `target`, `interval`)
among them. A push that failed and was made again, or a PG that began
its backfill anew without an interval change, would show here."""
from benchmarks.layer_metrics import recovery_spans

NAME = "recovery_objects_twice"
UNIT = "objects"
LAYER = "osd/pg+osd/ec_backend"
MOVES = "ops_s"


def read(ctx):
    spans = recovery_spans.recovers(ctx)
    if not spans:
        return None
    distinct = {(s["tags"]["oid"], s["tags"].get("target"),
                 s["tags"].get("interval")) for s in spans}
    return float(len(spans) - len(distinct))
