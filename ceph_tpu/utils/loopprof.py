"""The loop account: where an event loop's wall time goes, by layer.

While armed it times every callback the loop runs (`Handle._run` is the
seam ready queue, timers and I/O all pass through) and charges it to a
PART of a LABEL (`"msgr.rx_sock"`; a label without parts, `"store"`, is
its own): the innermost open tracer span or `tracer.section` of the task
being stepped (kept on the task as `loop_label`; the CM closes the
running interval and opens the next, so span time is SELF time), else
the file and name of the callback's code (`LABEL_OF_*`, the one table of
layers). A label's time is the sum of its parts'. Collector pauses go to
`gc`, time parked in `select` is `idle`, the loop's own machinery to the
callback it follows. Every 50 ms a `loop_slice` span and a
`loop_slice50` annotation in the profiler's trace; a 10 Hz
watchdog catches a callback that holds the loop 0.5 s: a `loop_pause`.
`tracer.enable()` arms the running loop, or the first a mapped span is
entered on; `profiler_enabled` arms without tracing. Disarmed, nothing
is installed: no hook, no `gc.callbacks` entry, no thread.
"""
from __future__ import annotations

import asyncio
import bisect
import collections
import gc
import sys
import threading
import time
import traceback
import types
import weakref
from asyncio import events as _events

from ceph_tpu.utils import flight, tracer
from ceph_tpu.utils.perf_counters import TYPE_GAUGE, PerfCountersCollection

LABELS = ("msgr", "client", "osd", "offload", "store", "harness",
          "background", "gc", "unattributed")
IDLE = "idle"
#: the labels that are read by part; `other` is charged, never computed
PARTS = {"msgr": ("rx_sock", "rx_alloc", "rx_frame", "codec", "tx_frame",
                  "tx_sock", "dispatch", "handler", "other"),
         "osd": ("pg", "ec", "subop", "queue", "scrub", "recovery",
                 "other")}
#: what the books are keyed by: every part, the labels that have none
#: (each its own one part), `idle`
KEYS = tuple(f"{lab}.{p}" for lab in LABELS for p in PARTS.get(lab, ())
             ) + tuple(lab for lab in LABELS if lab not in PARTS) + (IDLE,)
#: self time of a span. Sections (`tracer.section`) name their part
#: themselves: `msgr.rx_alloc`, `msgr.codec`, `msgr.tx_sock`,
#: `msgr.handler` (a handler of a message that carries no trace context)
LABEL_OF_SPAN = {
    "ms_dispatch": "msgr.handler", "rados_op": "client", "aio_op": "client",
    "osd_op": "osd.pg", "pg_op": "osd.pg", "ec_write": "osd.ec",
    "ec_read": "osd.ec", "ec_encode": "osd.ec", "ec_decode": "osd.ec",
    "ec_recover": "osd.recovery", "backfill_reserve": "osd.recovery",
    "offload_batch": "offload", "store_commit": "store",
    "scrub_round": "osd.scrub", "scrub_chunk": "osd.scrub"}
#: `ms_dispatch` covers the handler: the receiving daemon's, where known
#: (on an OSD: a shard serving a sub-op, the primary taking its replies)
LABEL_OF_SERVICE = {"osd": "osd.subop", "client": "client",
                    "mon": "background", "mgr": "background"}
#: (file, name of the code or None for any, part), first match wins: the
#: sockets are the messenger's (asyncio's transport reads and writes
#: them), the op queue the OSD's, its timers and the ticker our own
LABEL_OF_PATH = (
    ("/ceph_tpu/msg/messenger.py", "_read_loop", "msgr.rx_frame"),
    ("/ceph_tpu/msg/messenger.py", "_write_loop", "msgr.tx_frame"),
    ("/ceph_tpu/msg/messenger.py", "_dispatch_loop", "msgr.dispatch"),
    ("/asyncio/selector_events.py", "_read_ready", "msgr.rx_sock"),
    ("/asyncio/selector_events.py", "_write_ready", "msgr.tx_sock"),
    ("/ceph_tpu/msg/rxworker.py", "_reap", "msgr.rx_sock"),
    ("/ceph_tpu/msg/", None, "msgr.other"),
    ("/asyncio/selector_events.py", None, "msgr.other"),
    ("/asyncio/streams.py", None, "msgr.other"),
    ("/ceph_tpu/rados/", None, "client"),
    ("/ceph_tpu/osd/", "_heartbeat", "background"),
    ("/ceph_tpu/osd/", "_scrub_loop", "background"),
    ("/ceph_tpu/osd/", "_backfill_answer", "osd.recovery"),
    ("/ceph_tpu/osd/", None, "osd.other"),
    ("/ceph_tpu/utils/work_queue.py", None, "osd.queue"),
    ("/ceph_tpu/offload/", None, "offload"),
    ("/ceph_tpu/objectstore/", None, "store"),
    ("/ceph_tpu/mon/", None, "background"),
    ("/ceph_tpu/mgr/", None, "background"),
    ("/ceph_tpu/utils/loopprof.py", None, "background"),
    ("/benchmarks/", None, "harness"))

SLICE50_NS = 50_000_000         # a `loop_slice` span, and its annotation
TICK_S = 0.010                  # the lag ticker
LONG_NS = 10_000_000            # a callback worth a `loop:<label>` mark
PAUSE_NS = 500_000_000          # a callback that is a `loop_pause`
WATCH_NS = 100_000_000          # the watchdog's period
LAG_EDGES_MS = tuple(2.0 ** i for i in range(-2, 12))  # and one above

_ORIG_RUN = _events.Handle._run
_now, _current_task = time.perf_counter_ns, asyncio.current_task
_lock = threading.Lock()
_states: dict = {}              # loop -> _Acct, while armed
_tracked_loops = weakref.WeakSet()  # where a `config set` is marshalled to
_by_tracer = False              # tracer.enable() wants every loop armed
_books: dict[str, dict] = {}    # shard label -> ns by part, since reset
_gc_t0 = 0                      # the running collection's start, and
_gcs: collections.deque = collections.deque(maxlen=64)  # (end, ns) of late
_code_labels: dict = {}         # id(code object or type) -> (part, it)
_watchdog = None                # (thread, its stop event) while any loop


class _Acct:
    """One armed loop: every ns up to `mark` is charged in `acc`. A
    callback is charged when the next one starts (so is the loop's own
    machinery after it): the hook does nothing once the callback ran."""
    __slots__ = (               # one object, no dict: the hook's are first
        "acc", "cur", "mark", "t_cb", "handle", "n", "loop", "label",
        "owners", "thread_id", "cpu_clock", "acc50", "t50", "t1", "n50",
        "lag", "lag50", "cpus", "due", "selector", "parked",
        # the watchdog's: `n` last seen, its catch, worst lateness, last wake
        "seen", "pause", "late", "woke")

    def __init__(self, loop, label: str):
        self.loop, self.label, self.owners = loop, label, set()
        self.cur, self.handle, self.n, self.n50 = "unattributed", None, 0, 0
        self.due, self.selector, self.parked = 0.0, None, False
        self.seen, self.pause, self.late, self.woke = -1, None, 0, 0
        self.thread_id = threading.get_ident()
        self.cpu_clock = time.pthread_getcpuclockid(self.thread_id)
        self.acc = _books.setdefault(label, dict.fromkeys(KEYS, 0))
        self.acc50 = dict(self.acc)
        self.mark = self.t_cb = self.t50 = self.t1 = now = _now()
        self.lag = [0] * (len(LAG_EDGES_MS) + 1)    # since a reset
        self.lag50 = list(self.lag)
        self.cpus: collections.deque = collections.deque(
            [(now, time.clock_gettime_ns(self.cpu_clock))], maxlen=16)

    def switch(self, part: str) -> None:
        now = _now()
        self.acc[self.cur] += now - self.mark
        self.mark, self.cur = now, part


def _by_label(acc: dict) -> dict:
    """The books by label: a label's parts summed, `idle` with them."""
    by = dict.fromkeys(LABELS + (IDLE,), 0)
    for part, v in acc.items():
        by[part.partition(".")[0]] += v
    return by


def _label_of(cb, owner) -> str:
    """Part of a callback by its code's file and name; a task (`owner` of
    its step or wake-up) keeps it as `loop_label`, where an open span or
    section overrides it."""
    task = owner if isinstance(owner, asyncio.Task) else None
    if task is not None:
        key = getattr(task.get_coro(), "cr_code", None) or type(task)
    else:
        fn = cb.__func__ if type(cb) is types.MethodType else \
            getattr(cb, "func", cb)             # a partial's, or itself
        key = getattr(fn, "__code__", None) or type(cb)
    hit = _code_labels.get(id(key))     # hashing a code object walks it
    if hit is None:             # the table is walked once per key
        path = getattr(key, "co_filename", None) or \
            "/" + getattr(key, "__module__", "").replace(".", "/") + "/"
        name = getattr(key, "co_name", None)
        label = next((part for file, code, part in LABEL_OF_PATH
                      if file in path and code in (None, name)),
                     "unattributed")
        hit = _code_labels[id(key)] = (label, key)  # `key` lives: id is its
    label = hit[0]
    if task is not None:
        task.loop_label = label
    return label


def _enter(what):
    """A span CM's enter, or a section's (`what` is then its part):
    switch to the part (None: it moves none)."""
    if type(what) is str:
        label = what
    elif what.name == "ms_dispatch":
        label = LABEL_OF_SERVICE.get(what.service.partition(".")[0],
                                     LABEL_OF_SPAN["ms_dispatch"])
    else:
        label = LABEL_OF_SPAN.get(what.name)
    loop = _events._get_running_loop()
    if label is None or loop is None:
        return None
    st = _states.get(loop)
    if st is None:
        if not _by_tracer:
            return None
        st = install(loop, owner="tracer")      # deferred arming
    task = _current_task(loop)
    if task is not None:        # kept on the task for when it resumes
        back = getattr(task, "loop_label", None) or _label_of(None, task)
        task.loop_label = label
    else:
        back = st.cur
    st.switch(label)
    return st, task, back


def _exit(token) -> None:
    st, task, back = token
    if task is not None:
        task.loop_label = back
    st.switch(back)


def _run(self):
    """Installed as `asyncio.events.Handle._run` while any loop is armed."""
    st = _states.get(self._loop)
    if st is not None:
        now = _now()            # the callback before, and what followed
        st.acc[st.cur] += now - st.mark
        if now - st.t_cb > LONG_NS:
            _long_callback(st, now, now - st.t_cb)
        cb = self._callback
        owner = getattr(cb, "__self__", None)
        st.cur = getattr(owner, "loop_label", None) or _label_of(cb, owner)
        st.mark = st.t_cb = now
        st.handle = self
        st.n += 1
    return _ORIG_RUN(self)


def _wrap_select(st: _Acct) -> None:
    sel, orig = st.loop._selector, st.loop._selector.select

    def select(timeout=None):
        if timeout == 0:
            return orig(timeout)
        st.parked, t0 = True, _now()
        events = orig(timeout)
        idle = _now() - t0      # not the callback's before it
        st.acc[IDLE] += idle
        st.mark += idle
        st.t_cb += idle
        st.parked = False
        return events
    sel.select = select
    st.selector = (sel, select)


def _on_gc(phase: str, info: dict) -> None:
    global _gc_t0
    if phase == "start":
        _gc_t0 = _now()
        return
    now = _now()
    _gcs.append((now, now - _gc_t0))
    st = _states.get(_events._get_running_loop())
    if st is not None:          # out of the interrupted label, into gc
        st.acc["gc"] += now - _gc_t0
        st.mark += now - _gc_t0


def _tick(st: _Acct) -> None:
    """How late the ticker runs is what every hop of an op pays."""
    loop = _events._get_running_loop()
    if _states.get(loop) is not st:
        return                  # disarmed: the ticker stops itself
    now = loop.time()
    st.lag[bisect.bisect_left(LAG_EDGES_MS, (now - st.due) * 1e3)] += 1
    if st.mark - st.t50 >= SLICE50_NS:      # charged up to this tick
        _roll(st, st.mark)
    st.due = max(st.due + TICK_S, now + TICK_S / 2)
    loop.call_at(st.due, _tick, st)


def _annotation():
    """jax's TraceAnnotation, never imported here: parents stay off jax."""
    jax = sys.modules.get("jax")
    return getattr(getattr(jax, "profiler", None), "TraceAnnotation", None)


def _roll(st: _Acct, now: int) -> None:
    """50 ms: the slice as a `loop_slice` span (microseconds by label,
    and under `parts` by part of the labels that have them) and, for the
    profiler's trace, a `loop_slice50` annotation (by label); once a
    second the gauges."""
    parts = {k: (v - st.acc50[k]) / 1e3 for k, v in st.acc.items()}
    us = {k + "_us": v for k, v in _by_label(parts).items()}
    mark = _annotation()
    if mark is not None:
        with mark("loop_slice50", len_us=(now - st.t50) // 1000, pc_ns=now,
                  **{k: int(v) for k, v in us.items()}):
            pass
    tracer.record_span(
        "loop_slice", st.t50 / 1e9, (now - st.t50) / 1e3,
        dict(us, parts={k: v for k, v in parts.items() if "." in k},
             callbacks=st.n - st.n50, lag_edges_ms=LAG_EDGES_MS,
             lag_hist=[a - b for a, b in zip(st.lag, st.lag50)]),
        service=st.label)
    st.t50, st.acc50, st.n50, st.lag50 = now, dict(st.acc), st.n, list(st.lag)
    if now - st.t1 >= 1e9:
        st.t1 = now
        _publish()


def _long_callback(st: _Acct, now: int, took: int) -> None:
    """The callback that just ended (`st.handle`, `st.cur`) held the loop
    over 10 ms: a `loop:<label>` mark in the profiler's trace (its stats
    carry the interval: a TraceMe cannot be backdated). Over 0.5 s: a
    `loop_pause` (was it the collector, Python, a block, the machine)."""
    mark = _annotation()
    if mark is not None:
        with mark(f"loop:{st.cur.partition('.')[0]}", dur_us=took // 1000,
                  pc_ns=now):
            pass
    if took < PAUSE_NS:
        return
    caught, st.pause = st.pause or {}, None
    cpu0 = caught.get("cpu0")   # sampled up to 0.1 s before the pause
    facts = {
        "duration_s": round(took / 1e9, 4), "label": st.cur,
        "callback": repr(st.handle), "stack": caught.get("stack"),
        "gc_s": round(sum(ns for end, ns in _gcs if end > st.t_cb) / 1e9, 4),
        "cpu_s": None if cpu0 is None else
        round((time.clock_gettime_ns(st.cpu_clock) - cpu0) / 1e9, 4),
        # a watchdog that has not woken since is late by that much too
        "watchdog_late_s": round(max(
            st.late, now - max(st.woke, st.t_cb) - WATCH_NS, 0) / 1e9, 4)}
    flight.record("loop_pause", st.label, **facts)
    tracer.record_span("loop_pause", st.t_cb / 1e9, took / 1e3, facts,
                       service=st.label)


def _watch(stop: threading.Event) -> None:
    """10 Hz: prune closed loops, sample CPU clocks, catch a held loop."""
    while True:
        t0 = _now()
        if stop.wait(WATCH_NS / 1e9):
            return
        now = _now()
        for loop, st in list(_states.items()):
            if loop.is_closed():
                uninstall(loop, owner=None)
                continue
            cpu = time.clock_gettime_ns(st.cpu_clock)
            if st.n != st.seen or st.parked:    # the loop turns, or rests
                st.seen, st.pause, st.late = st.n, None, 0
            elif st.pause is None and now - st.t_cb >= PAUSE_NS:
                before = [c for t, c in st.cpus if t <= st.t_cb]
                st.pause = {
                    "stack": [     # innermost first, less loop machinery
                        f"{f.filename}:{f.lineno} in {f.name}"
                        for f in reversed(traceback.extract_stack(
                            sys._current_frames().get(st.thread_id)))
                        if "/asyncio/" not in f.filename][:12],
                    "cpu0": before[-1] if before else None}
            st.late, st.woke = max(st.late, now - t0 - WATCH_NS), now
            st.cpus.append((now, cpu))


def install(loop: asyncio.AbstractEventLoop | None = None,
            owner: str = "operator") -> _Acct:
    """Arm `loop` (default: the running one) for `owner`, on its thread."""
    global _watchdog
    loop = loop or asyncio.get_running_loop()
    from ceph_tpu.utils import reactor
    with _lock:
        st = _states.get(loop)
        if st is None:          # loops outside a reactor share one label
            st = _Acct(loop, reactor.shard_label(loop) or "loop0")
            _wrap_select(st)
            st.due = loop.time() + TICK_S
            loop.call_at(st.due, _tick, st)
            if not _states:
                _events.Handle._run = _run
                gc.callbacks.append(_on_gc)
                tracer.set_account(_enter, _exit)
                stop = threading.Event()
                _watchdog = (threading.Thread(
                    target=_watch, args=(stop,), daemon=True,
                    name="loopprof-watchdog"), stop)
                _watchdog[0].start()
            _states[loop] = st
        st.owners.add(owner)
    return st


def uninstall(loop: asyncio.AbstractEventLoop | None = None,
              owner: str | None = "operator") -> None:
    """Disarm `loop` for `owner` (None: everyone); any thread may."""
    global _watchdog
    loop = loop or asyncio.get_running_loop()
    with _lock:
        st = _states.get(loop)
        if st is None:
            return
        st.owners.discard(owner)
        if st.owners and owner is not None:
            return
        del _states[loop]
        st.switch(st.cur)       # the running callback, so far
        if st.selector is not None and \
                st.selector[0].__dict__.get("select") is st.selector[1]:
            del st.selector[0].select
        if _states:
            return
        _events.Handle._run = _ORIG_RUN
        gc.callbacks.remove(_on_gc)
        if not _by_tracer:
            tracer.set_account(None, None)
        (thread, stop), _watchdog = _watchdog, None
    stop.set()
    if thread is not threading.current_thread():
        thread.join(2.0)


def tracer_armed(on: bool) -> None:
    """tracer.enable()/disable(): arm this loop now, others lazily."""
    global _by_tracer
    _by_tracer = bool(on)
    loop = _events._get_running_loop()
    if on and loop is not None:
        install(loop, owner="tracer")
    for lp in [] if on else list(_states):
        uninstall(lp, owner="tracer")
    if on or not _states:
        tracer.set_account(*((_enter, _exit) if on
                             else (None, None)))


def installed_loops() -> list:
    """Live loops still armed: the conftest leak gate wants none."""
    return [lp for lp in list(_states) if not lp.is_closed()]


def _shard(wall_us: float, busy_us: float) -> dict:
    return {"wall_us": round(wall_us, 1), "busy_us": round(busy_us, 1),
            "loop_busy_fraction": round(busy_us / (wall_us or 1), 4)}


def shard_stats() -> dict[str, dict]:
    """{"shard0": {"wall_us", "busy_us", "loop_busy_fraction"}, ...}."""
    return {lbl: _shard(sum(d.values()) / 1e3,
                        (sum(d.values()) - d[IDLE]) / 1e3)
            for lbl, d in sorted(_books.items())}


def merge_shard_stats(*parts: dict[str, dict]) -> dict[str, dict]:
    """Per-process `shard_stats()` merged by shard label (same: summed)."""
    merged: dict[str, list] = {}
    for part in parts:
        for lbl, d in (part or {}).items():
            m = merged.setdefault(lbl, [0.0, 0.0])
            m[0] += float(d.get("wall_us", 0))
            m[1] += float(d.get("busy_us", 0))
    return {lbl: _shard(*m) for lbl, m in sorted(merged.items())}


def shard_busy_skew(shards: dict[str, dict] | None = None) -> float:
    """(max-min)/max busy fraction across shards: 0 is balanced."""
    shards = shard_stats() if shards is None else shards
    fr = [d["loop_busy_fraction"] for d in shards.values()
          if d["wall_us"] > 0]
    if len(fr) < 2 or max(fr) <= 0:
        return 0.0
    return round((max(fr) - min(fr)) / max(fr), 4)


def dump() -> dict:
    """`profile dump`: us by label and by part, busy fraction, the armed
    loops' lag."""
    st = _states.get(_events._get_running_loop())
    if st is not None:
        st.switch(st.cur)       # the running callback, so far
    parts = {k: sum(d[k] for d in _books.values()) for k in KEYS}
    labels = {k: round(v / 1e3, 1) for k, v in _by_label(parts).items()}
    wall = sum(labels.values())
    shards = shard_stats()
    live = list(_states.values())
    return {"enabled": bool(installed_loops()), "labels_us": labels,
            "parts_us": {k: round(v / 1e3, 1) for k, v in parts.items()
                         if "." in k},
            "wall_us": round(wall, 1),
            "loop_busy_fraction": round((wall - labels[IDLE]) / wall, 4)
            if wall else 0.0,
            "callbacks": sum(st.n for st in live),
            "shards": shards, "shard_busy_skew": shard_busy_skew(shards),
            "lag_edges_ms": list(LAG_EDGES_MS),
            "lag_hist": [sum(c) for c in zip(*(st.lag for st in live))]}


def reset() -> dict:
    """Admin-socket `profile reset`: zero the books."""
    cleared = dump()["wall_us"]
    with _lock:
        for books in list(_books.values()) + \
                [st.acc50 for st in _states.values()]:
            books.update(dict.fromkeys(books, 0))
        for st in _states.values():
            st.lag, st.lag50 = [0] * len(st.lag), [0] * len(st.lag)
            # the open slice starts anew where its books do: one that
            # straddled a reset was as long as before and held less
            st.t50 = st.mark
    return {"cleared_wall_us": cleared}


def perf():
    """The `loopprof` logger: gauges published each second while armed."""
    coll = PerfCountersCollection.instance()
    with _lock:
        pc = coll.get("loopprof") or coll.create("loopprof")
        for name in ["loop_busy_fraction", "shard_busy_skew"] + \
                [f"loop_busy_fraction_{label}" for label in _books]:
            if name not in pc._types:
                pc.add(name, type=TYPE_GAUGE, description="busy/accounted "
                       "wall time: all loops, a shard's, (max-min)/max")
    return pc


def _publish() -> None:
    pc, shards = perf(), shard_stats()
    pc.set("loop_busy_fraction", dump()["loop_busy_fraction"])
    pc.set("shard_busy_skew", shard_busy_skew(shards))
    for label, d in shards.items():
        pc.set(f"loop_busy_fraction_{label}", d["loop_busy_fraction"])


def register_config(config) -> None:
    """Declare `profiler_enabled` and arm live on a `config set`."""
    from ceph_tpu.utils.config import ConfigError, Option
    try:
        config.declare(Option("profiler_enabled", "bool", False,
                              "arm the loop account (loop time by layer, "
                              "lag, pauses) without full tracing"))
    except ConfigError:
        pass                            # already declared by another daemon

    def _on_change(_name: str, value) -> None:
        arm = install if value else uninstall
        loop = _events._get_running_loop()
        if loop is not None:
            return arm(loop)
        for lp in list(_tracked_loops):     # admin-socket thread: marshal
            if not lp.is_closed():
                lp.call_soon_threadsafe(arm, lp)

    config.add_observer(("profiler_enabled",), _on_change)


def maybe_install(config=None) -> None:
    """Arm the running loop when enabled; track it for a later set."""
    try:
        _tracked_loops.add(asyncio.get_running_loop())
        if config.get("profiler_enabled"):
            install()
    except Exception:
        pass                            # no config, or no such option
