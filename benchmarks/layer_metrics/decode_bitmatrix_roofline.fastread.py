"""`decode_bitmatrix_roofline` in the cell that reads fast past stragglers: the codec program's share of its roofline over batches of r = 1, 2 and 3, each at its true r.
The accepted reader under a name of this cell's own, because the
accepted entry's `workloads` is not a `model_config` PR's to append
to."""
from benchmarks.layer_metrics import decode_bitmatrix_roofline

NAME = "decode_bitmatrix_roofline.fastread"
UNIT = decode_bitmatrix_roofline.UNIT
LAYER = decode_bitmatrix_roofline.LAYER
MOVES = decode_bitmatrix_roofline.MOVES
read = decode_bitmatrix_roofline.read
