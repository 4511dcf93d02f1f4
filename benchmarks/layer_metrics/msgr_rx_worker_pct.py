"""Share of the bytes all messengers received that the receive worker's
native thread took off the sockets (`rx_worker_bytes` over
`rx_direct_bytes` and `rx_spill_bytes`): the part of the receive, and of
its crc32c, that did not run on an event loop (`ceph_tpu/msg/rxworker.py`).
0.0 where every body is under the worker's line."""
from benchmarks.layer_metrics import msgr_rx

NAME = "msgr_rx_worker_pct"
UNIT = "%"
LAYER = "msg/messenger"
MOVES = "ops_s"


def read(ctx):
    got = msgr_rx.deltas(ctx)
    key = "rx_worker_bytes"
    if got is None or key not in ctx.open["msgr"] \
            or key not in ctx.close["msgr"]:
        return None
    direct, spill, _recvs = got
    return 100.0 * (ctx.close["msgr"][key] - ctx.open["msgr"][key]) \
        / (direct + spill)
