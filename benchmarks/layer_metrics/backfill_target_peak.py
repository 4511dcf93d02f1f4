"""The most backfill reservations held at once on one target: each from
its `backfill_reserve`'s end to its `backfill_done`, or to the close
where the window closed over it. `osd_max_backfills` bounds it (1 on
the cell's deployment)."""
from benchmarks.layer_metrics import recovery_spans

NAME = "backfill_target_peak"
UNIT = "count"
LAYER = "osd/reserver"
MOVES = "op_p95_ms"


def read(ctx):
    events = [e for e in recovery_spans.reservation_events(
        ctx, t_close=float("inf")) if e[2] == "remote"]
    if not events:
        return None
    held, peak = {}, 0
    for _t, osd, _role, delta in sorted(events, key=lambda e: (e[0], e[3])):
        held[osd] = held.get(osd, 0) + delta
        peak = max(peak, held[osd])
    return float(peak)
