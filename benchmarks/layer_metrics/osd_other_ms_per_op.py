"""Loop CPU an op in the rest of `ceph_tpu/osd/` by path, outside any
span. Charged, never computed by subtraction."""
from benchmarks.layer_metrics import loop_parts

NAME = "osd_other_ms_per_op"
UNIT = "ms/op"
LAYER = "osd/pg+osd/ec_backend"
MOVES = "ops_s"


def read(ctx):
    return loop_parts.ms_per_op(ctx, "osd.other")
