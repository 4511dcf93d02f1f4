"""From the profiler's trace (`.xplane.pb`) to numbers.

The harness wraps the measured window in a `jax.profiler.TraceAnnotation`
named `bench_window`; everything here is clipped to that interval.

  busy_s       union of the intervals in which an operation ran on the
               device (the "XLA Ops" line of each `/device:TPU:n`
               plane), averaged over the devices
  programs     device seconds by XLA program (the "XLA Modules" line):
               the name jax gives a jitted function, `jit_<function>`,
               without the run id XLA appends, so it survives a change
               of fusion names
  device_ops   the ten operations that took most device time
  idle_gaps    the ten longest intervals with no operation on the
               device, each named after what the host was doing: the
               shortest host event (a runtime call on any thread, or
               one of the harness's own annotations) that covers at
               least half of the gap; where none does, the gap lies in
               host Python that made no runtime call

`jax.profiler.ProfileData` reads the file; nothing else is needed.
"""
from __future__ import annotations

import glob
import json
import os
import re

WINDOW = "bench_window"
NO_HOST_EVENT = "host_python_no_runtime_call"
_RUN_ID = re.compile(r"\(\d+\)$")
_HLO = re.compile(r"^%?([\w.\-]+) = ([a-z]+\d*)\[([\d,]*)\]")


def op_name(hlo: str) -> str:
    """The "XLA Ops" line names an event by its whole HLO instruction;
    keep the instruction's name and its result's type and shape:
    `convert_reduce_fusion_s32_3_128_4096`."""
    m = _HLO.match(hlo)
    if m is None:
        return hlo.split(" = ")[0].lstrip("%")
    dims = m.group(3).replace(",", "_")
    return f"{m.group(1)}_{m.group(2)}_{dims}" if dims else \
        f"{m.group(1)}_{m.group(2)}"


def peaks_for(device_kind: str) -> dict:
    """The published peaks of `device_kind`; an unknown device is an
    error, not a default."""
    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "peaks.json")) as f:
        peaks = json.load(f)
    if device_kind not in peaks or device_kind == "source":
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"benchmarks/peaks.json")
    return peaks[device_kind]


def _union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for lo, hi in sorted(intervals):
        if out and lo <= out[-1][1]:
            out[-1][1] = max(out[-1][1], hi)
        else:
            out.append([lo, hi])
    return [(lo, hi) for lo, hi in out]


def _clip(start: float, dur: float, lo: float, hi: float):
    a, b = max(start, lo), min(start + dur, hi)
    return (a, b) if b > a else None


def _is_device(plane_name: str) -> bool:
    return plane_name.startswith("/device:TPU:")


def name_gap(a: float, b: float,
             host: list[tuple[str, float, float]]) -> str:
    """What the host was doing while the device idled from a to b: the
    shortest host event (name, start, end) that covers at least half of
    the gap."""
    best = None
    for name, s, e in host:
        cover = min(e, b) - max(s, a)
        if cover >= (b - a) / 2 and (best is None or e - s < best[1]):
            best = (name, e - s)
    return best[0] if best else NO_HOST_EVENT


def reduce_file(path: str) -> dict:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    planes = list(data.planes)
    host: list[tuple[str, float, float]] = []      # (name, start, end)
    window = None
    for plane in planes:
        if _is_device(plane.name):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name == WINDOW:
                    window = (ev.start_ns, ev.start_ns + ev.duration_ns)
                elif ev.duration_ns > 0:
                    host.append((ev.name, ev.start_ns,
                                 ev.start_ns + ev.duration_ns))
    if window is None:
        raise ValueError(f"{path}: no {WINDOW!r} annotation in the trace")
    lo, hi = window

    busy_s = []
    op_time: dict[str, float] = {}
    programs: dict[str, float] = {}
    busy_all: list[tuple[float, float]] = []
    for plane in planes:
        if not _is_device(plane.name):
            continue
        intervals = []
        for line in plane.lines:
            if line.name == "XLA Ops":
                for ev in line.events:
                    c = _clip(ev.start_ns, ev.duration_ns, lo, hi)
                    if c:
                        intervals.append(c)
                        name = op_name(ev.name)
                        op_time[name] = op_time.get(name, 0.0) \
                            + (c[1] - c[0]) / 1e9
            elif line.name == "XLA Modules":
                for ev in line.events:
                    c = _clip(ev.start_ns, ev.duration_ns, lo, hi)
                    if c:
                        name = _RUN_ID.sub("", ev.name)
                        programs[name] = programs.get(name, 0.0) \
                            + (c[1] - c[0]) / 1e9
        merged = _union(intervals)
        busy_s.append(sum(b - a for a, b in merged) / 1e9)
        busy_all.extend(merged)
    if not busy_s:
        raise ValueError(f"{path}: no /device:TPU:n plane in the trace")

    # idle gaps: where no device ran an operation (with one chip, the
    # complement of its busy intervals)
    gaps = []
    at = lo
    for a, b in _union(busy_all):
        if a > at:
            gaps.append((at, a))
        at = max(at, b)
    if hi > at:
        gaps.append((at, hi))
    gaps.sort(key=lambda g: g[0] - g[1])
    host = [h for h in host if h[2] > lo and h[1] < hi]
    idle = [[name_gap(a, b, host), (b - a) / 1e9] for a, b in gaps[:10]]
    top = sorted(op_time.items(), key=lambda kv: -kv[1])[:10]
    return {"window_s": (hi - lo) / 1e9,
            "busy_s": sum(busy_s) / len(busy_s),
            "devices": len(busy_s),
            "programs": programs,
            "breakdown": {"device_ops": [[n, t] for n, t in top],
                          "idle_gaps": idle}}


def reduce_dir(profile_dir: str) -> dict:
    """The one `.xplane.pb` the profiler left under `profile_dir`."""
    found = sorted(glob.glob(os.path.join(
        profile_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if len(found) != 1:
        raise ValueError(f"{profile_dir}: {len(found)} .xplane.pb files, "
                         f"one expected")
    return reduce_file(found[0])
