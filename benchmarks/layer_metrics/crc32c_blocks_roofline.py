"""The crc program's share of its roofline: the least time the chip
could take for the window's crc batches, over the device time the trace
gives the program `jit__crc_blocks_jit`.

What a block of N bytes is reckoned at is the work, not the
implementation:
  bytes  N + 4            read the block once, write its crc
  ops    2 * 8N * 32      the GF(2) product of its 8N bits with the
                          (8N, 32) matrix, int8 multiply-add
and the larger of the two times at `peaks.json` (at N = 4096 on a v5e:
5.0 ns by HBM, 5.3 ns by the MXU, so int8 binds, narrowly). Counted are
the `blocks` of each batch's `offload_batch` span, the jobs' own: the
program pads a batch to a power of two, expands bytes to bit planes and
fills 32 of the MXU's columns, and all of that shows as a lower share,
as it should."""
from benchmarks.layer_metrics import crc_batches

NAME = "crc32c_blocks_roofline"
UNIT = "%"
LAYER = "ops/crc32c kernel"
MOVES = "ops_s"

PROGRAM = "jit__crc_blocks_jit"


def least_seconds(blocks: float, block_size: int, peaks: dict) -> dict:
    by_bytes = blocks * (block_size + 4) / peaks["hbm_bytes_per_s"]
    by_ops = blocks * 2 * (8 * block_size) * 32 / peaks["int8_ops_per_s"]
    return {"hbm": by_bytes, "int8": by_ops}


def read(ctx):
    if ctx.trace is None or ctx.peaks is None:
        return None
    batches = [t for t in crc_batches.tags(ctx)
               if "blocks" in t and "block_size" in t]
    kernel_s = ctx.trace["programs"].get(PROGRAM, 0.0)
    if not batches or not kernel_s:
        return None
    least = sum(max(least_seconds(t["blocks"], t["block_size"],
                                  ctx.peaks).values()) for t in batches)
    return 100.0 * least / kernel_s
