"""CPU time of the worker's native threads on the frames they sent
(crc32c and sendmsg: `tx_worker_cpu_ns`) over the window: the share of
one core that the loop's relief was bought with. With
`msgr_rx_worker_busy_pct`, over the threads there are
(`ceph_tpu/msg/rxworker.py`, `WORKERS`), it says how near a thread is to
being the limit."""
NAME = "msgr_tx_worker_busy_pct"
UNIT = "%"
LAYER = "msg/messenger"
MOVES = "ops_s"


def read(ctx):
    before, after = ctx.open.get("msgr", {}), ctx.close.get("msgr", {})
    key = "tx_worker_cpu_ns"
    if key not in before or key not in after or ctx.window_s <= 0:
        return None
    return 100.0 * (after[key] - before[key]) / 1e9 / ctx.window_s
