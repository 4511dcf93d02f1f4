"""Share of the loop's wall time (`loop_slice.osd_us`) in the OSD's op path:
the op queue, `osd_op`, `pg_op`, `ec_write`, `ec_read`, `ec_encode`, and
messages handed to an OSD (sub-ops among them)."""
from benchmarks.layer_metrics import loop_share

NAME = "loop_osd_pct"
UNIT = "%"
LAYER = "osd/pg+osd/ec_backend"
MOVES = "ops_s"


def read(ctx):
    return loop_share.share(ctx, "osd")
