"""Share of the payload bytes the messengers' write loops framed that
left by reference (`tx_direct_bytes` over it and `tx_copied_bytes`, in the
`msgr` perf logger, as deltas over the window): sent by a scatter
`sendmsg` from where the segments lie. The rest, frames under the spill
size and every frame of a secure or compressed session, was copied once
into a packed blob."""
NAME = "msgr_tx_direct_pct"
UNIT = "%"
LAYER = "msg/messenger"
MOVES = "ops_s"

KEYS = ("tx_direct_bytes", "tx_copied_bytes")


def read(ctx):
    """None where the program has no such counters (a parent whose
    frames all leave as packed blobs) or sent nothing."""
    before, after = ctx.open.get("msgr", {}), ctx.close.get("msgr", {})
    if any(k not in before or k not in after for k in KEYS):
        return None
    direct, copied = (after[k] - before[k] for k in KEYS)
    if direct + copied <= 0:
        return None
    return 100.0 * direct / (direct + copied)
