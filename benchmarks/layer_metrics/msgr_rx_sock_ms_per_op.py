"""Loop CPU an op in the selector's read callback (`_read_ready`):
`recv_into`, `Endpoint.get_buffer`, `Endpoint.buffer_updated`; the
kernel's copy and the first touch of a fresh body's pages land here."""
from benchmarks.layer_metrics import loop_parts

NAME = "msgr_rx_sock_ms_per_op"
UNIT = "ms/op"
LAYER = "msg/messenger"
MOVES = "ops_s"


def read(ctx):
    return loop_parts.ms_per_op(ctx, "msgr.rx_sock")
