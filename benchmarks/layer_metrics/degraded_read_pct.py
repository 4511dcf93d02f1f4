"""Share of the primaries' reads that had to reconstruct: `ec_decode`
spans over `ec_read` spans, both inside the window. A healthy read
interleaves its k data chunks and opens no `ec_decode`."""
NAME = "degraded_read_pct"
UNIT = "%"
LAYER = "osd/ec_backend"
MOVES = "ops_s"


def read(ctx):
    reads = ctx.spans.get("ec_read", [])
    decodes = ctx.spans.get("ec_decode", [])
    if not reads or not decodes:
        return None
    return 100.0 * len(decodes) / len(reads)
