"""`ec_decode_ms` in the cell that reads fast past stragglers: a reconstruction at r = 1, 2 or 3 under the survivor set that the stragglers of the moment left.
The accepted reader under a name of this cell's own, because the
accepted entry's `workloads` is not a `model_config` PR's to append
to."""
from benchmarks.layer_metrics import ec_decode_ms

NAME = "ec_decode_ms.fastread"
UNIT = ec_decode_ms.UNIT
LAYER = ec_decode_ms.LAYER
MOVES = ec_decode_ms.MOVES
read = ec_decode_ms.read
