"""What the `loop_*` readers share: the event loop's wall time by label,
from the `loop_slice` spans the loop account (`ceph_tpu/utils/loopprof.py`)
closes every 50 ms. A slice's tags are its microseconds by label,
`idle_us` among them; together they are its length."""
LABELS = ("msgr", "client", "osd", "offload", "store", "harness",
          "background", "gc", "unattributed", "idle")


def totals(ctx):
    """label -> microseconds over the window's slices; None where the
    program closes no such span (a parent without the account)."""
    slices = [s["tags"] for s in ctx.spans.get("loop_slice", [])
              if "idle_us" in s["tags"]]
    if not slices:
        return None
    return {k: sum(t.get(k + "_us", 0.0) for t in slices) for k in LABELS}


def share(ctx, label):
    """The label's share of the loop's wall time, in percent."""
    by = totals(ctx)
    if by is None or not sum(by.values()):
        return None
    return 100.0 * by[label] / sum(by.values())
