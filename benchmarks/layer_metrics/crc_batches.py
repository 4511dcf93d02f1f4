"""What the `crc_*` readers of spans share: the window's `offload_batch`
spans of kind `crc` that ran on a device lane (the offload service tags
them `blocks`, `block_size`, `padded_blocks`; a host-native crc batch
has `device` "host" and is not read here)."""
import statistics


def tags(ctx):
    """The tag dictionaries of the window's device crc batches."""
    return [s["tags"] for s in ctx.spans.get("offload_batch", [])
            if s["tags"].get("kind") == "crc"
            and s["tags"].get("device", "host") != "host"]


def median_ms(ctx, hops):
    """Median over those batches of the sum of `hops` (tags in
    microseconds), in milliseconds; None where no batch has them all."""
    sums = [sum(t[h] for h in hops) for t in tags(ctx)
            if all(h in t for h in hops)]
    return statistics.median(sums) / 1e3 if sums else None
