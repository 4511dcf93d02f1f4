"""What the recovery cell's readers share: the window's spans of
recovery and backfill, and the window's whole seconds.

`ec_recover` (one a shard rebuilt, on the primary: `oid`, `pgid`,
`target`, `interval`, `need`, `helpers`, `chunks`), `backfill_reserve`
(one a PG's wait for its slots, on the primary: `pgid`, `target` (the
OSDs asked), `kind` backfill / log, `local_us`, `remote_us`, `rejects`,
`state` granted / interval_change / aborted) and `backfill_done` (a
marker when the PG lets its slots go: `pgid`, `target`, `objects`,
`bytes`, `held_us`, `state` done / interval_change / aborted). A grant
with no `backfill_done` after it was held to the window's close. A
program that opens no such span, or an `ec_recover` without `oid`, as
the parent of the PR that brought them, has nothing here and every
reader built on this returns None there.

Seconds are counted from the harness's `bench_open` marker on the
tracer's own clock; the last, broken second of a window is left out.
"""


def recovers(ctx):
    """The window's `ec_recover` spans that say what they rebuilt."""
    return [s for s in ctx.spans.get("ec_recover", [])
            if "oid" in s["tags"]]


def _end(span):
    return span["start"] + span["duration_us"] / 1e6


def seconds(ctx):
    """(t_open, n): the window's first instant on the spans' clock and
    its whole seconds; None where the marker is missing."""
    marks = ctx.spans.get("bench_open", [])
    if not marks:
        return None
    return marks[0]["start"], int(ctx.window_s)


def active_seconds(ctx):
    """The window's whole seconds in which an `ec_recover` span ended,
    as a set of indices; None where there is nothing to read, or the
    window has no whole second."""
    spans, clock = recovers(ctx), seconds(ctx)
    if not spans or clock is None or not clock[1]:
        return None
    t_open, n = clock
    return {int(_end(s) - t_open) for s in spans} & set(range(n))


def per_second(ctx, name):
    """Spans of `name` that ended in each whole second of the window."""
    t_open, n = seconds(ctx)
    counts = [0] * n
    for s in ctx.spans.get(name, []):
        i = int(_end(s) - t_open)
        if 0 <= i < n:
            counts[i] += 1
    return counts


def reserves(ctx, state=None):
    return [s for s in ctx.spans.get("backfill_reserve", [])
            if state is None or s["tags"].get("state") == state]


def dones(ctx, state=None):
    return [s for s in ctx.spans.get("backfill_done", [])
            if state is None or s["tags"].get("state") == state]


def holds(ctx):
    """(t_grant, t_release or None, primary, pgid, targets) of every
    reservation granted in the window: from its `backfill_reserve`'s
    end to the first `backfill_done` of that primary and PG after it,
    None where there is none (held to the close)."""
    done_at: dict = {}
    for d in sorted(dones(ctx), key=lambda s: s["start"]):
        done_at.setdefault((d["service"], d["tags"].get("pgid")),
                           []).append(d["start"])
    out = []
    for r in sorted(reserves(ctx, "granted"), key=_end):
        later = [t for t in done_at.get((r["service"],
                                         r["tags"].get("pgid")), [])
                 if t >= _end(r)]
        out.append((_end(r), later[0] if later else None, r["service"],
                    r["tags"].get("pgid"),
                    list(r["tags"].get("target") or [])))
    return out


def reservation_events(ctx, t_close=float("inf")):
    """The holds as `reference_recovery.check_reservations` takes them:
    (time, osd, role, +1 / -1), a local slot on the primary and a
    remote one on each target, given back at `t_close` where the
    window closed over them."""
    events = []
    for t0, t1, service, _pgid, targets in holds(ctx):
        t1 = t_close if t1 is None else t1
        who = [(int(service.partition(".")[2]), "local")] + \
            [(osd, "remote") for osd in targets]
        for osd, role in who:
            events += [(t0, osd, role, +1), (t1, osd, role, -1)]
    return events
