"""Share of the payload bytes the messengers' write loops framed that the
send worker's native thread put on the sockets (`tx_worker_bytes` over
`tx_direct_bytes` and `tx_copied_bytes`, deltas over the window): the part
of the send, and of its crc32c, that did not run on an event loop
(`ceph_tpu/msg/rxworker.py`). 0.0 where every frame is under the worker's
line."""
NAME = "msgr_tx_worker_pct"
UNIT = "%"
LAYER = "msg/messenger"
MOVES = "ops_s"

KEYS = ("tx_worker_bytes", "tx_direct_bytes", "tx_copied_bytes")


def read(ctx):
    """None where the program has no such counters (a parent without the
    send worker) or sent nothing."""
    before, after = ctx.open.get("msgr", {}), ctx.close.get("msgr", {})
    if any(k not in before or k not in after for k in KEYS):
        return None
    worker, direct, copied = (after[k] - before[k] for k in KEYS)
    if direct + copied <= 0:
        return None
    return 100.0 * worker / (direct + copied)
