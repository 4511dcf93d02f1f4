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

The account books itself too, as an "of which": a slice's `instr` tag
says how many of its microseconds were the instruments' own
(`by_kind`: `span`, a span's life outside its body; `section`, a
section's way in and out; `hook`, `_run`'s own lines; `roll`, the
ticker and the slice's closing) and which parts had been charged them
(`in_part`). No part's time moves: the tag only says how much of a part
is its observer. One span or section in `tracer.TIMED_EVERY` is timed,
between clock reads of its own at both ends of both stretches, and
stands for that many. `hook` is CALIBRATED, not timed: the callbacks of
the slice times a unit cost measured once, when the first loop is armed,
by running the hook and asyncio's own `Handle._run` on a no-op handle.
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
_running_loop = _events._get_running_loop
_lock = threading.Lock()
_states: dict = {}              # loop -> _Acct, while armed
_tracked_loops = weakref.WeakSet()  # where a `config set` is marshalled to
_by_tracer = False              # tracer.enable() wants every loop armed
_books: dict[str, dict] = {}    # shard label -> ns by part, since reset
_gc_t0 = 0                      # the running collection's start, and
_gcs: collections.deque = collections.deque(maxlen=64)  # (end, ns) of late
_code_labels: dict = {}         # id(code object or type) -> (part, it)
_watchdog = None                # (thread, its stop event) while any loop
_hook_unit_ns = 0.0             # what `_run` adds to a callback, calibrated
INSTR_KINDS = ("span", "section", "hook", "roll")
CB_EVERY = 16                   # a callback in so many is counted by part


class _Acct:
    """One armed loop: every ns up to `mark` is charged in `acc`. A
    callback is charged when the next one starts (so is the loop's own
    machinery after it): the hook does nothing once the callback ran."""
    __slots__ = (               # one object, no dict: the hook's are first
        "acc", "cur", "mark", "t_cb", "handle", "n", "task", "loop", "label",
        "owners", "thread_id", "cpu_clock", "acc50", "t50", "t1", "n50",
        "lag", "lag50", "cpus", "due", "selector", "parked",
        # the instruments' own, since the slice opened: the open stretch's
        # start, ns by kind and by part, spans and sections closed,
        # callbacks by part (one in `CB_EVERY`)
        "i0", "i_kind", "i_part", "n_spans", "n_sections", "cbs",
        # the watchdog's: `n` last seen, its catch, worst lateness, last wake
        "seen", "pause", "late", "woke")

    def __init__(self, loop, label: str):
        self.loop, self.label, self.owners = loop, label, set()
        self.cur, self.handle, self.n, self.n50 = "unattributed", None, 0, 0
        self.task = None        # the task the running callback steps
        self.due, self.selector, self.parked = 0.0, None, False
        self.seen, self.pause, self.late, self.woke = -1, None, 0, 0
        self.i0, self.n_spans, self.n_sections = 0, 0, 0
        self.i_kind, self.i_part, self.cbs = \
            dict.fromkeys(INSTR_KINDS, 0), {}, {}
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


def _label_of(cb, owner, st: _Acct | None = None) -> str:
    """Part of a callback by its code's file and name; a task (`owner` of
    its step or wake-up) keeps it as `loop_label`, where an open span or
    section overrides it, and is noted on `st` as the one being stepped."""
    task = owner if isinstance(owner, asyncio.Task) else None
    if st is not None:
        st.task = task
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


#: `ms_dispatch`'s part by the receiving daemon's name, as met
_dispatch_labels: dict[str, str] = {}
_current = tracer._current


def _state_for(label):
    """The running loop's state; one armed now where the tracer wants
    every loop armed and this is the first mapped span entered on it."""
    loop = _events._get_running_loop()
    st = _states.get(loop)
    if st is None and loop is not None and _by_tracer and label is not None:
        st = install(loop, owner="tracer")      # deferred arming
    return st


class _Span(tracer.Span):
    """What `tracer.span()` makes while an account is armed: entered, the
    span becomes the current context and the loop switches to its part
    (a span the table does not map stays in the part it is in: the
    interval is split, nothing moves). The loop's state knows the task
    that is being stepped since the callback began (`_run`). `_timed`:
    the state, where this span is the one in `tracer.TIMED_EVERY` whose
    own code is timed; the way in and the way out are charged to the
    part outside."""

    __slots__ = ("_st", "_task", "_back", "_timed")

    def __enter__(self):
        self._token = _current.set((self.trace_id, self.span_id, self.flags))
        name = self.name
        if name == "ms_dispatch":
            label = _dispatch_labels.get(self.service)
            if label is None:
                label = _dispatch_labels[self.service] = \
                    LABEL_OF_SERVICE.get(self.service.partition(".")[0],
                                         LABEL_OF_SPAN["ms_dispatch"])
        else:
            label = LABEL_OF_SPAN.get(name)
        st = self._st = _states.get(_running_loop()) or \
            _state_for(label)
        if st is not None:
            back = self._back = st.cur  # a task's `loop_label` where one steps
            if label is None:
                label = back
            task = self._task = st.task
            if task is not None:        # kept on the task for when it resumes
                task.loop_label = label
            now = _now()
            st.acc[back] += now - st.mark
            st.mark, st.cur = now, label
            if self._timed is not None:
                _close(self._timed, "span", back)
        return self

    def __exit__(self, et, ev, tb) -> bool:
        timed = self._timed
        if timed is not None:
            timed.i0 = _now()
        _current.reset(self._token)
        st, back = self._st, None
        if st is not None:
            back = self._back
            if self._task is not None:
                self._task.loop_label = back
            now = _now()
            st.acc[st.cur] += now - st.mark
            st.mark, st.cur = now, back
            st.n_spans += 1
        if et is not None:
            self.tags.setdefault("error", f"{et.__name__}: {ev}")
        self._finish()
        if timed is not None:
            _close(timed, "span", back)
        return False


class _SectionCM:
    """`tracer.section()`'s CM while an account is armed: the stretch is
    charged to `part`, the rest of the callback to its own. Timed as a
    span is; its way out is its own part's."""

    __slots__ = ("_part", "_st", "_task", "_back", "_timed")

    def __init__(self, part: str, timed):
        self._part = part
        self._timed = timed

    def __enter__(self) -> None:
        part = self._part
        st = self._st = _states.get(_running_loop()) or \
            _state_for(part)
        if st is not None:
            back = self._back = st.cur
            task = self._task = st.task
            if task is not None:
                task.loop_label = part
            now = _now()
            st.acc[back] += now - st.mark
            st.mark, st.cur = now, part
            if self._timed is not None:
                _close(self._timed, "section", back)

    def __exit__(self, et, ev, tb) -> bool:
        st = self._st
        if st is not None:
            timed = self._timed
            if timed is not None:
                timed.i0 = _now()
            back = self._back
            if self._task is not None:
                self._task.loop_label = back
            now = _now()
            st.acc[st.cur] += now - st.mark
            st.mark, st.cur = now, back
            st.n_sections += 1
            if timed is not None:
                _close(timed, "section", self._part)
        return False


def _open():
    """A stretch of a span's or a section's own code starts here, on
    this thread's loop if it is armed (its state is returned)."""
    st = _states.get(_running_loop())
    if st is not None:
        st.i0 = _now()          # a collection inside the stretch moves it
    return st


def _close(st: _Acct, kind: str, part: str | None) -> None:
    """The stretch ends: booked as `kind`, for `tracer.TIMED_EVERY` of
    its like, against the part that was charged it (None: the running
    one)."""
    ns = (_now() - st.i0) * tracer.TIMED_EVERY
    if part is None:
        part = st.cur
    st.i_kind[kind] += ns
    st.i_part[part] = st.i_part.get(part, 0) + ns


def _closed() -> None:
    """A span finished outside a CM, on this thread's loop if armed."""
    st = _states.get(_running_loop())
    if st is not None:
        st.n_spans += 1


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
        label = getattr(owner, "loop_label", None)
        if label is not None:
            st.task = owner     # only a task carries one
        else:                   # a plain callback's code, met before?
            hit = _code_labels.get(id(getattr(
                getattr(cb, "__func__", cb), "__code__", None)))
            if hit is None:
                label = _label_of(cb, owner, st)
            else:
                label, st.task = hit[0], None
        st.cur = label
        st.mark = st.t_cb = now
        st.handle = self
        st.n += 1
        if not st.n % CB_EVERY:
            st.cbs[label] = st.cbs.get(label, 0) + 1
    return _ORIG_RUN(self)


class _NoLoop:
    """What a handle made for the calibration asks of its loop."""

    @staticmethod
    def get_debug() -> bool:
        return False

    is_closed = get_debug       # (the watchdog asks every loop it finds)


def _calibrate_hook(rounds: int = 2000) -> float:
    """ns a callback that `_run` adds to asyncio's own `Handle._run`:
    both run on one no-op handle, the hook with a state of its own that
    no loop has, the least of five rounds each. Called once a process,
    as its first loop is armed and before that loop's books open."""
    loop = _NoLoop()
    handle = _events.Handle(_NoLoop.get_debug, (), loop)
    st = object.__new__(_Acct)
    st.acc, st.cur, st.cbs = dict.fromkeys(KEYS, 0), "unattributed", {}
    st.mark = st.t_cb = _now()
    st.handle, st.n = None, 0

    def least(run) -> float:
        best = None
        for _ in range(5):
            t0 = _now()
            for _ in range(rounds):
                run(handle)
            took = (_now() - t0) / rounds
            best = took if best is None else min(best, took)
        return best
    plain = least(_ORIG_RUN)
    _states[loop] = st
    try:
        hooked = least(_run)
    finally:
        del _states[loop]
    return max(hooked - plain, 0.0)


def hook_unit_ns() -> float:
    """The calibrated unit, 0.0 before any loop was armed."""
    return _hook_unit_ns


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
        st.i0 += now - _gc_t0   # and out of an instrument's open stretch


def _tick(st: _Acct) -> None:
    """How late the ticker runs is what every hop of an op pays."""
    loop = _events._get_running_loop()
    if _states.get(loop) is not st:
        return                  # disarmed: the ticker stops itself
    st.i0 = _now()              # the ticker's own: `roll`, booked at its end
    now = loop.time()
    st.lag[bisect.bisect_left(LAG_EDGES_MS, (now - st.due) * 1e3)] += 1
    if st.mark - st.t50 >= SLICE50_NS:      # charged up to this tick
        _roll(st, st.mark)
    st.due = max(st.due + TICK_S, now + TICK_S / 2)
    loop.call_at(st.due, _tick, st)
    ns = _now() - st.i0         # (in the slice this tick belongs to)
    st.i_kind["roll"] += ns
    st.i_part[st.cur] = st.i_part.get(st.cur, 0) + ns


def _annotation():
    """jax's TraceAnnotation, never imported here: parents stay off jax."""
    jax = sys.modules.get("jax")
    return getattr(getattr(jax, "profiler", None), "TraceAnnotation", None)


def _instr(st: _Acct, callbacks: int, busy_us: float) -> dict:
    """The slice's `instr` tag, and the books it is made from emptied. A
    timed stretch stands for sixteen, so one that the machine held up
    can outweigh its slice: what is over the slice's busy time stays in
    the books for the next one, by kind and by part alike."""
    kinds, in_part = st.i_kind, st.i_part
    hook = callbacks * _hook_unit_ns
    kinds["hook"] = kinds.get("hook", 0) + hook
    seen = sum(st.cbs.values())
    for part, n in (st.cbs if seen else {st.cur: 1}).items():
        in_part[part] = in_part.get(part, 0) + hook * n / (seen or 1)
    whole = sum(kinds.values())
    now = min(1.0, busy_us * 1e3 / whole) if whole else 1.0
    tag = {"by_kind": {k: v * now / 1e3 for k, v in kinds.items()},
           "in_part": {k: v * now / 1e3 for k, v in in_part.items()},
           "spans": st.n_spans, "sections": st.n_sections}
    st.i_kind = {k: v * (1.0 - now) for k, v in kinds.items()}
    st.i_part = {k: v * (1.0 - now) for k, v in in_part.items()} \
        if now < 1.0 else {}
    st.cbs, st.n_spans, st.n_sections = {}, 0, 0
    return tag


def _roll(st: _Acct, now: int) -> None:
    """50 ms: the slice as a `loop_slice` span (microseconds by label,
    under `parts` by part of the labels that have them, under `instr`
    the instruments' own among them) and, for the profiler's trace, a
    `loop_slice50` annotation (by label); once a second the gauges."""
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
             instr=_instr(st, st.n - st.n50,
                          sum(v for k, v in parts.items() if k != IDLE)),
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


_HOOKS = (_Span, _SectionCM, _open, _close, _closed)


def install(loop: asyncio.AbstractEventLoop | None = None,
            owner: str = "operator") -> _Acct:
    """Arm `loop` (default: the running one) for `owner`, on its thread."""
    global _watchdog, _hook_unit_ns
    loop = loop or asyncio.get_running_loop()
    from ceph_tpu.utils import reactor
    with _lock:
        st = _states.get(loop)
        if st is None:          # loops outside a reactor share one label
            if not _hook_unit_ns:   # once a process, before the books open
                _hook_unit_ns = _calibrate_hook()
            st = _Acct(loop, reactor.shard_label(loop) or "loop0")
            task = _current_task(loop) \
                if _events._get_running_loop() is loop else None
            if task is not None:    # armed inside its step
                st.cur = getattr(task, "loop_label", None) or \
                    _label_of(None, task, st)
                st.task = task
            _wrap_select(st)
            st.due = loop.time() + TICK_S
            loop.call_at(st.due, _tick, st)
            if not _states:
                _events.Handle._run = _run
                gc.callbacks.append(_on_gc)
                tracer.set_account(*_HOOKS)
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
            tracer.set_account()
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
        tracer.set_account(*(_HOOKS if on else ()))


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
    loops' lag, and what the hook was calibrated to add to a callback."""
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
            "hook_unit_ns": round(_hook_unit_ns, 1),
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
            st.i_kind, st.i_part, st.cbs = dict.fromkeys(st.i_kind, 0), {}, {}
            st.n_spans = st.n_sections = 0
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
