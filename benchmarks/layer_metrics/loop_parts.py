"""What the `*_ms_per_op` readers share: the event loop's CPU an op, by
part of a label. The loop account (`ceph_tpu/utils/loopprof.py`) charges
every callback to a part (`msgr.rx_sock`, `osd.ec`; a label's
microseconds are the sum of its parts') and a `loop_slice` span carries
them under its `parts` tag. Milliseconds an op and not a share: with 16
ops in flight the loop stays full, so a share hardly moves while
`ops_s` is the reciprocal of the loop's CPU an op."""
from benchmarks.layer_metrics import loop_share


def _parts(ctx):
    """The `parts` tags of the window's slices; empty on a parent whose
    account has no second level."""
    return [s["tags"]["parts"] for s in ctx.spans.get("loop_slice", [])
            if "parts" in s["tags"]]


def ms_per_op(ctx, part):
    """The part's microseconds over the window's slices / ops completed
    / 1000; None where no slice carries `parts`, the part is not among
    them, or no op completed."""
    slices = _parts(ctx)
    if not ctx.ops or not any(part in p for p in slices):
        return None
    return sum(p.get(part, 0.0) for p in slices) / ctx.ops / 1000.0


def busy_ms_per_op(ctx):
    """Every label but `idle`, the whole of which the parts are shares,
    on the same condition: a parent reports none of the family."""
    by = loop_share.totals(ctx)
    if not ctx.ops or by is None or not _parts(ctx):
        return None
    return (sum(by.values()) - by["idle"]) / ctx.ops / 1000.0
