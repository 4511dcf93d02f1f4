"""`ec_read_ms` in the cell that reads fast past stragglers: the reads of a pool that reads fast: the gather of the first k of ten replies, and a reconstruction in half of them.
The accepted reader under a name of this cell's own, because the
accepted entry's `workloads` is not a `model_config` PR's to append
to."""
from benchmarks.layer_metrics import ec_read_ms

NAME = "ec_read_ms.fastread"
UNIT = ec_read_ms.UNIT
LAYER = ec_read_ms.LAYER
MOVES = ec_read_ms.MOVES
read = ec_read_ms.read
