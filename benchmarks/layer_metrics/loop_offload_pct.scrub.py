"""`loop_offload_pct` where the only batches are the scrub's crc
batches: what the digests take of the loop (linger, flush, stacking,
`offload_batch` round the staged dispatch). The accepted reader under a
name of this cell's own, as `ec_read_ms.scrub`."""
from benchmarks.layer_metrics import loop_offload_pct

NAME = "loop_offload_pct.scrub"
UNIT = loop_offload_pct.UNIT
LAYER = loop_offload_pct.LAYER
MOVES = loop_offload_pct.MOVES
read = loop_offload_pct.read
