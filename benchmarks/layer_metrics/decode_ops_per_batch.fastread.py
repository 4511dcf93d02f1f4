"""`decode_ops_per_batch` in the cell that reads fast past stragglers: decode jobs a device batch where two reads in flight seldom share an erasure pattern.
The accepted reader under a name of this cell's own, because the
accepted entry's `workloads` is not a `model_config` PR's to append
to."""
from benchmarks.layer_metrics import decode_ops_per_batch

NAME = "decode_ops_per_batch.fastread"
UNIT = decode_ops_per_batch.UNIT
LAYER = decode_ops_per_batch.LAYER
MOVES = decode_ops_per_batch.MOVES
read = decode_ops_per_batch.read
