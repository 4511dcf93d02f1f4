"""`decode_handoff_ms` in the cell that reads fast past stragglers: the waits round a decode batch, the linger in front of a lone job among them, under mixed patterns.
The accepted reader under a name of this cell's own, because the
accepted entry's `workloads` is not a `model_config` PR's to append
to."""
from benchmarks.layer_metrics import decode_handoff_ms

NAME = "decode_handoff_ms.fastread"
UNIT = decode_handoff_ms.UNIT
LAYER = decode_handoff_ms.LAYER
MOVES = decode_handoff_ms.MOVES
read = decode_handoff_ms.read
