"""What the `fastread_*` readers share: the window's `ec_read` spans of
reads that asked every live shard at once (the backend tags such a span
`fast`, with `shards_used`, the k positions it answered from, and
`late`, the replies it did not wait for, beside `shards_asked`). A
program without `fast_read`, as the parent of the PR that brought it,
tags nothing so, and every reader built on this returns None there."""


def fast_reads(ctx):
    """The tag dictionaries of the window's fast reads."""
    return [s["tags"] for s in ctx.spans.get("ec_read", [])
            if s["tags"].get("fast")]
