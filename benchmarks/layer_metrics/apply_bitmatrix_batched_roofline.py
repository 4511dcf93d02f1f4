"""The codec program's share of its roofline: the least time the chip
could take for the window's encode batches, over the device time the
trace gives the program `jit__apply_bitmatrix_batched_jit`.

What a batch is reckoned at is the algorithm's minimum for its
unpadded shape (b stripes, k data chunks, r parity chunks of n bytes):
  bytes  b*(k+r)*n         read the data once, write the parity once
  ops    2*(8r)*(8k)*b*n   the GF(2) matrix product, int8 multiply-add
The offload service books each batch's unpadded input bytes b*k*n under
its device lane; both bounds are linear in it, so the sums suffice. The
program pads b to a power of two and expands bytes to bit planes: that
waste shows as a lower share, as it should. Which bound binds is
printed by the harness (HBM for every k, r in use: 11 B against 3072
int8 ops per input byte at k=8, r=3)."""
NAME = "apply_bitmatrix_batched_roofline"
UNIT = "%"
LAYER = "ops/rs_codec kernel"
MOVES = "ops_s"

PROGRAM = "jit__apply_bitmatrix_batched_jit"


def least_seconds(input_bytes: float, k: int, r: int, peaks: dict) -> dict:
    by_bytes = input_bytes * (k + r) / k / peaks["hbm_bytes_per_s"]
    by_ops = input_bytes / k * 2 * (8 * r) * (8 * k) / peaks["int8_ops_per_s"]
    return {"hbm": by_bytes, "int8": by_ops}


def read(ctx):
    if ctx.trace is None or ctx.peaks is None:
        return None
    kernel_s = ctx.trace["programs"].get(PROGRAM, 0.0)
    input_bytes = ctx.device_delta("bytes")
    if not kernel_s or not input_bytes:
        return None
    pool = ctx.cell.config["pool"]
    least = least_seconds(input_bytes, pool["k"], pool["m"], ctx.peaks)
    return 100.0 * max(least.values()) / kernel_s
