"""Content the deep scrub digested a second, all OSDs together: the
`bytes` of the window's `scrub_chunk` spans over the window. A shard is
counted where it is read and hashed, so a PG's round adds k+m shards."""
NAME = "scrub_hashed_mib_s"
UNIT = "MiB/s"
LAYER = "osd/scrub"
MOVES = "ops_s"


def read(ctx):
    chunks = ctx.spans.get("scrub_chunk", [])
    if not chunks:
        return None
    return sum(s["tags"]["bytes"] for s in chunks) / ctx.window_s / 2 ** 20
