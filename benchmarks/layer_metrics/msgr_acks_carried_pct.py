"""Share of the acks all messengers sent that left in the header of a
MESSAGE frame (`acks_carried_tx`) and not as an ACK frame of their own
(`ack_frames_tx`): how often telling the peer what is done cost no frame
to pack, checksum, read and parse at either end."""

NAME = "msgr_acks_carried_pct"
UNIT = "%"
LAYER = "msg/messenger"
MOVES = "ops_s"
KEYS = ("acks_carried_tx", "ack_frames_tx")


def read(ctx):
    """None where the program has no such counters (a parent whose
    every ack is a frame), where no op completed, and where no ack left
    inside the window."""
    before, after = ctx.open.get("msgr", {}), ctx.close.get("msgr", {})
    if not ctx.ops or any(k not in before or k not in after for k in KEYS):
        return None
    carried, framed = (after[k] - before[k] for k in KEYS)
    if carried + framed <= 0:
        return None
    return 100.0 * carried / (carried + framed)
