"""`decode_device_call_ms` in the cell that reads fast past stragglers: the staged dispatch of a decode batch of one job, mostly.
The accepted reader under a name of this cell's own, because the
accepted entry's `workloads` is not a `model_config` PR's to append
to."""
from benchmarks.layer_metrics import decode_device_call_ms

NAME = "decode_device_call_ms.fastread"
UNIT = decode_device_call_ms.UNIT
LAYER = decode_device_call_ms.LAYER
MOVES = decode_device_call_ms.MOVES
read = decode_device_call_ms.read
