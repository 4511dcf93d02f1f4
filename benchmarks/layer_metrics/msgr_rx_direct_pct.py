"""Share of the bytes all messengers received that the kernel wrote
straight into a frame's own body buffer (`rx_direct_bytes` over it and
`rx_spill_bytes`); the rest went through a connection's spill buffer and
was copied once more."""
from benchmarks.layer_metrics import msgr_rx

NAME = "msgr_rx_direct_pct"
UNIT = "%"
LAYER = "msg/messenger"
MOVES = "ops_s"


def read(ctx):
    got = msgr_rx.deltas(ctx)
    if got is None:
        return None
    direct, spill, _recvs = got
    return 100.0 * direct / (direct + spill)
