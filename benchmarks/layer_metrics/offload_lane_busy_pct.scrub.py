"""`offload_lane_busy_pct` where the lanes carry crc batches alone. The
accepted reader under a name of this cell's own, as `ec_read_ms.scrub`;
None where no batch reached a device lane in the window (a parent that
finishes no round)."""
from benchmarks.layer_metrics import offload_lane_busy_pct

NAME = "offload_lane_busy_pct.scrub"
UNIT = offload_lane_busy_pct.UNIT
LAYER = offload_lane_busy_pct.LAYER
MOVES = offload_lane_busy_pct.MOVES
read = offload_lane_busy_pct.read
