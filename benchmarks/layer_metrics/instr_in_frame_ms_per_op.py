"""What of the messenger's per-frame Python is the observer's: the
instruments' loop CPU an op that had been charged to `msgr.rx_frame`,
`msgr.tx_frame`, `msgr.dispatch` and `msgr.handler`
(`loop_slice.instr.in_part`), so that those four `*_ms_per_op` can be
read net."""
from benchmarks.layer_metrics import loop_instr

NAME = "instr_in_frame_ms_per_op"
UNIT = "ms/op"
LAYER = "event loop (all daemons)"
MOVES = "ops_s"
PARTS = ("msgr.rx_frame", "msgr.tx_frame", "msgr.dispatch", "msgr.handler")


def read(ctx):
    return loop_instr.ms_per_op(ctx, "in_part", PARTS)
