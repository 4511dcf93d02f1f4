"""What the `enc_*` readers share: the window's `offload_batch` spans
of kind `enc` (the offload service tags every batch span with its
`kind`), as `decode_batches` has the decodes'; and the encodes' three
counters (`enc_jobs`, `enc_batches`, `enc_bytes` of `svc.stats`). A
program without the counters or the tags, as the parent of the PR that
brought them, gives every reader built on this nothing to read and it
returns None there."""
import statistics

COUNTERS = ("enc_jobs", "enc_batches", "enc_bytes")
#: the other kinds' batch counters: a window in which one of them moved
#: shared the link and the codec program with the encodes
OTHERS = ("dec_batches", "crc_batches")


def tags(ctx):
    """The tag dictionaries of the window's encode batches."""
    return [s["tags"] for s in ctx.spans.get("offload_batch", [])
            if s["tags"].get("kind") == "enc"]


def median_ms(ctx, hops):
    """Median over the encode batches of the sum of `hops` (tags in
    microseconds), in milliseconds; None where no batch has them all."""
    sums = [sum(t[h] for h in hops) for t in tags(ctx)
            if all(h in t for h in hops)]
    return statistics.median(sums) / 1e3 if sums else None


def deltas(ctx):
    """The encodes' counters over the window, or None where the program
    has none or no encode batch ended in it."""
    before, after = ctx.open.get("offload", {}), ctx.close.get("offload", {})
    if any(k not in d for d in (before, after) for k in COUNTERS):
        return None
    out = {k: after[k] - before[k] for k in COUNTERS}
    return out if out["enc_batches"] else None


def alone(ctx):
    """True where no decode or device crc batch ended in the window:
    the link's bytes and the codec program's time are the encodes'."""
    before, after = ctx.open.get("offload", {}), ctx.close.get("offload", {})
    return all(after.get(k, 0) == before.get(k, 0) for k in OTHERS)
