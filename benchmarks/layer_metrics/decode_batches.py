"""What the `decode_*` readers share: the window's `offload_batch` spans
of kind `dec` (the offload service tags every batch span with its
`kind`; a decode batch also carries `r` and `pattern`). A program
without the tag, as the parent of the PR that brought it, has no such
spans and every reader built on this returns None there."""
import statistics


def tags(ctx):
    """The tag dictionaries of the window's decode batches."""
    return [s["tags"] for s in ctx.spans.get("offload_batch", [])
            if s["tags"].get("kind") == "dec"]


def median_ms(ctx, hops):
    """Median over the decode batches of the sum of `hops` (tags in
    microseconds), in milliseconds; None where no batch has them all."""
    sums = [sum(t[h] for h in hops) for t in tags(ctx)
            if all(h in t for h in hops)]
    return statistics.median(sums) / 1e3 if sums else None
