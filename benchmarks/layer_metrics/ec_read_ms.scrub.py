"""`ec_read_ms` where a scrub runs beside the reads: the accepted
reader (median `ec_read` span), under a name of this cell's own,
because the accepted entry's `workloads` is not a `model_config` PR's
to append to. The parent opens the same spans and reports it too."""
from benchmarks.layer_metrics import ec_read_ms

NAME = "ec_read_ms.scrub"
UNIT = ec_read_ms.UNIT
LAYER = ec_read_ms.LAYER
MOVES = ec_read_ms.MOVES
read = ec_read_ms.read
