"""What the `msgr_ctrl_*` / `msgr_sends_*` readers share: the counters of
the messenger's write and keepalive loops (`ceph_tpu/msg/messenger.py`)
in the `msgr` perf logger, as deltas over the window."""
KEYS = ("ctrl_frames_tx", "tx_sends")


def deltas(ctx):
    """(control frames framed, sends) inside the window; None where the
    program has no such counters (a parent whose every frame is a send
    of its own)."""
    before, after = ctx.open.get("msgr", {}), ctx.close.get("msgr", {})
    if any(k not in before or k not in after for k in KEYS):
        return None
    return tuple(after[k] - before[k] for k in KEYS)
