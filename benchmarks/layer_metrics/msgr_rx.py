"""What the `msgr_rx_*`/`msgr_recvs_*` readers share: the receive counters
of the messenger's endpoint (`ceph_tpu/msg/transport.py`) in the `msgr`
perf logger, as deltas over the window."""
KEYS = ("rx_direct_bytes", "rx_spill_bytes", "rx_recvs")


def deltas(ctx):
    """(direct bytes, spill bytes, recvs) inside the window; None where
    the program has no such counters (a parent on asyncio's streams) or
    received nothing."""
    before, after = ctx.open.get("msgr", {}), ctx.close.get("msgr", {})
    if any(k not in before or k not in after for k in KEYS):
        return None
    direct, spill, recvs = (after[k] - before[k] for k in KEYS)
    if direct + spill <= 0:
        return None
    return direct, spill, recvs
