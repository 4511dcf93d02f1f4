"""`recv_into` calls that returned data (`rx_recvs`: one wake-up of a
reader at most, each) per MiB all messengers received: how many turns of
the loop a payload takes on its way in."""
from benchmarks.layer_metrics import msgr_rx

NAME = "msgr_recvs_per_mib"
UNIT = "recvs/MiB"
LAYER = "msg/messenger"
MOVES = "op_p50_ms"


def read(ctx):
    got = msgr_rx.deltas(ctx)
    if got is None:
        return None
    direct, spill, recvs = got
    return recvs / ((direct + spill) / 2 ** 20)
