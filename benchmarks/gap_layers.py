"""The ten longest device-idle gaps of a traced run, by layer.

    python3 -m benchmarks.gap_layers <run's --out directory>

`trace_reduce` names a gap after the shortest host event that covers
half of it, and where the host made no runtime call the name is
`host_python_no_runtime_call`. The loop account
(`ceph_tpu/utils/loopprof.py`) drops a `loop_slice50` annotation into
the profiler's trace every 50 ms whose stats are the loop's microseconds
by label since the last one, so the slices are on the device's clock by
construction: a gap's make-up is the sum of the slices that overlap it,
the two at its edges pro rata. A callback over 10 ms leaves a
`loop:<label>` mark there too (annotations cannot be backdated, so each
is an instant whose stats carry the interval it closes). This file
reads; it edits nothing, and `breakdown.idle_gaps` is `trace_reduce`'s.
"""
from __future__ import annotations

import glob
import json
import os
import sys

from benchmarks import trace_reduce

SLICE = "loop_slice50"
MARK = "loop:"


def slices_and_marks(planes) -> tuple[list, list]:
    """([(start_ns, end_ns, {label: us})], [(name, start_ns, end_ns)])
    from the host planes. An annotation is entered at the end of what
    it describes; its `len_us` / `dur_us` stat reaches back."""
    slices, marks = [], []
    for plane in planes:
        if trace_reduce._is_device(plane.name):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name != SLICE and not ev.name.startswith(MARK):
                    continue
                stats = dict(ev.stats)
                if ev.name == SLICE:
                    by = {k[:-3]: float(v) for k, v in stats.items()
                          if k.endswith("_us") and k != "len_us"}
                    slices.append((ev.start_ns - 1e3 * stats["len_us"],
                                   ev.start_ns, by))
                elif "dur_us" in stats:
                    marks.append((ev.name, ev.start_ns
                                  - 1e3 * stats["dur_us"], ev.start_ns))
    return sorted(slices, key=lambda s: s[0]), marks


def make_up(a: float, b: float, slices: list) -> dict[str, float]:
    """label -> seconds of the gap from a to b (ns), each overlapping
    slice counted by the share of it that lies inside the gap."""
    out: dict[str, float] = {}
    for lo, hi, by in slices:
        cover = min(hi, b) - max(lo, a)
        if cover <= 0 or hi <= lo:
            continue
        for label, us in by.items():
            out[label] = out.get(label, 0.0) + us * cover / (hi - lo) / 1e6
    return out


def gaps_of(planes) -> tuple[tuple, list]:
    """(window, device-idle gaps longest first), as `trace_reduce` finds
    them: the complement of the union of the "XLA Ops" intervals."""
    window = next(((ev.start_ns, ev.start_ns + ev.duration_ns)
                   for p in planes if not trace_reduce._is_device(p.name)
                   for ln in p.lines for ev in ln.events
                   if ev.name == trace_reduce.WINDOW), None)
    if window is None:
        raise ValueError(f"no {trace_reduce.WINDOW!r} annotation")
    lo, hi = window
    busy = [c for p in planes if trace_reduce._is_device(p.name)
            for ln in p.lines if ln.name == "XLA Ops" for ev in ln.events
            if (c := trace_reduce._clip(ev.start_ns, ev.duration_ns, lo, hi))]
    gaps, at = [], lo
    for a, b in trace_reduce._union(busy):
        if a > at:
            gaps.append((at, a))
        at = max(at, b)
    if hi > at:
        gaps.append((at, hi))
    return window, sorted(gaps, key=lambda g: g[0] - g[1])


def report(path: str, top: int = 10) -> list[dict]:
    from jax.profiler import ProfileData

    planes = list(ProfileData.from_file(path).planes)
    slices, marks = slices_and_marks(planes)
    (lo, _hi), gaps = gaps_of(planes)
    rows = []
    for a, b in gaps[:top]:
        by = make_up(a, b, slices)
        rows.append({
            "at_s": (a - lo) / 1e9, "gap_s": (b - a) / 1e9,
            "covered_s": sum(by.values()),
            "by_label_s": dict(sorted(by.items(), key=lambda kv: -kv[1])),
            "marks": [[n, (e - s) / 1e9] for n, s, e in marks
                      if s < b and e > a]})
    return rows


def main(argv: list[str]) -> int:
    found = sorted(glob.glob(os.path.join(
        argv[0], "**", "*.xplane.pb"), recursive=True))
    if len(found) != 1:
        raise SystemExit(f"{argv[0]}: {len(found)} .xplane.pb files, "
                         f"one expected")
    for row in report(found[0]):
        print(json.dumps(row))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
