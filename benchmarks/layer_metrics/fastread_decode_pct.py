"""Share of the fast reads that had to reconstruct: `ec_decode` spans
over `ec_read` spans tagged `fast`, both inside the window. A fast read
answers from the first k chunks that come; where those are the k data
positions it interleaves them and opens no `ec_decode`, and where a
straggler kept a data position back it rebuilds that position from a
parity that came instead. With every shard's reply held back one time in
ten the arithmetic says 52% (`reference_fastread.expected_decode_share`).
Fewer is better for the same tail: a decode is the price of not
waiting."""
from benchmarks.layer_metrics import fastread_spans

NAME = "fastread_decode_pct"
UNIT = "%"
LAYER = "osd/ec_backend"
MOVES = "ops_s"


def read(ctx):
    reads = fastread_spans.fast_reads(ctx)
    if not reads:
        return None
    return 100.0 * len(ctx.spans.get("ec_decode", [])) / len(reads)
