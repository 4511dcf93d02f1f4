"""`decode_link_bytes_per_byte` in the cell that reads fast past stragglers: bytes over the link per byte read where half of the reads reconstruct.
The accepted reader under a name of this cell's own, because the
accepted entry's `workloads` is not a `model_config` PR's to append
to."""
from benchmarks.layer_metrics import decode_link_bytes_per_byte

NAME = "decode_link_bytes_per_byte.fastread"
UNIT = decode_link_bytes_per_byte.UNIT
LAYER = decode_link_bytes_per_byte.LAYER
MOVES = decode_link_bytes_per_byte.MOVES
read = decode_link_bytes_per_byte.read
