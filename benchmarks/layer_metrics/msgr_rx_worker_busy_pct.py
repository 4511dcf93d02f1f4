"""CPU time of the receive worker's native thread on its bodies (recv
and crc32c: `rx_worker_cpu_ns`) over the window: the share of one core
that the loop's relief was bought with. Near 100 the one thread is the
limit (`ceph_tpu/msg/rxworker.py`, `WORKERS`)."""
NAME = "msgr_rx_worker_busy_pct"
UNIT = "%"
LAYER = "msg/messenger"
MOVES = "ops_s"


def read(ctx):
    before, after = ctx.open.get("msgr", {}), ctx.close.get("msgr", {})
    key = "rx_worker_cpu_ns"
    if key not in before or key not in after or ctx.window_s <= 0:
        return None
    return 100.0 * (after[key] - before[key]) / 1e9 / ctx.window_s
