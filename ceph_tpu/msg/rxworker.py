"""The event loop's end of the messenger's socket worker (the native
threads at the end of native/ec_native.cc; upstream's AsyncMessenger
`Worker`, src/msg/async/Stack.h, as far as the interpreter's lock makes
it worth having: system calls and arithmetic).

Receives: an endpoint that has a body of `LINE` bytes or more to receive
pauses its transport and `submit`s the socket with the body's buffer; the
thread `recv`s the rest of the body, and no byte more, and checks the
segments' crc32c as the bytes arrive. Sends: an endpoint whose write loop
has a plain-crc frame of `LINE` bytes of payload or more, and whose
transport has nothing queued, `submit_tx`s the frame's segments with a
dup of the socket that it keeps for the connection's life; the thread
computes each segment's crc32c a chunk ahead of the bytes that leave,
writes it into the job's own header buffer, and `sendmsg`s from where
the parts lie, waiting for `EPOLLOUT` itself where the socket is full, so
a send holds up nobody's receive though both ends of a connection may
live in this process. Either way the thread posts a completion and the
loop is woken once, through an eventfd it watches (`_Port`), reaps every
completion that is there with one call and resolves each job's future.
What the thread is handed stays referenced here (`_jobs`: a body's
buffer; a frame's head, header buffer and every part of every segment)
until it is reaped or `cancel` has returned, and `cancel` returns only
once the thread has let go of the fd and of every buffer.

`WORKERS` threads a process, a connection's jobs on one of them by its
socket's number (upstream pins a connection to a Worker), started at the
first large body or frame, stopped when the last endpoint that used them
is lost (a messenger's shutdown closes its own) and at exit; a forked
child starts its own. No option selects any of this: where the library
is missing or is not Linux's, `available()` is false and the endpoint's
own paths are the paths.

The constants are drawn from two probes on the chip's host: one asyncio
loop holding both ends of eight loopback connections that stream frames
of one body each, the loop's CPU a body with the transport receiving (or
sending) -> with this worker. Receives (my chip run, PR 52, call 1;
`PERF.md` §6 has the table): a hand-over (submit and reap: a `dup`, two
`epoll_ctl`, two eventfd calls, which that host's sandbox kernel prices
at tens of microseconds each) costs the loop 0.12-0.23 ms there, and a
ping-pong on an idle link 0.17-0.25 ms of wall (a sleeping thread is
woken, then the loop):

    body       pages kept (a write's)    pages recycled (a read's)
    128 KiB    0.43-0.46 -> 0.45-0.47    0.23-0.34 -> 0.30
    256 KiB    0.69-0.72 -> 0.65         0.34-0.56 -> 0.36-0.40
    512 KiB    1.10-1.12 -> 0.76-0.78    0.48-0.87 -> 0.47-0.55
    1 MiB      1.74-1.77 -> 0.97-1.04    1.33-1.37 -> 0.61-0.67
    4 MiB      5.70-5.74 -> 1.88-1.91    2.58-2.85 -> 1.41

Under 512 KiB the loop wins nothing or loses; at 512 KiB a receive on new
pages is worth three hand-overs and one on recycled pages breaks even.
Sends (my chip run, PR 54, call 1; `PERF.md` §6): a hand-over (the
submit and its share of the reap: one eventfd write, one eventfd read, no
`dup` and no `epoll_ctl` on the loop's thread) costs the loop 0.08-0.11
ms; the loop's CPU a body, both ends on it, the transport sending -> this
worker, with the receive the worker's as in the tree from 512 KiB up,
and the thread's own CPU a body (crc a 256 KiB chunk ahead -> a 1 MiB
chunk ahead, which is what the native side does: fewer `sendmsg`s):

    body       receive on the worker     receive on the loop       the thread
    128 KiB    0.30-0.32 -> 0.31-0.34    0.26-0.28 -> 0.26-0.34    0.05-0.10
    256 KiB    0.41-0.44 -> 0.36-0.39    0.38-0.43 -> 0.29-0.44    0.07-0.20
    512 KiB    0.46-0.55 -> 0.43-0.47    0.52-0.58 -> 0.41-0.62    0.11-0.29
    1 MiB      0.67-0.73 -> 0.54-0.77    0.74-0.87 -> 0.54-0.95    0.43-0.52 -> 0.24-0.35
    4 MiB      1.56-1.80 -> 0.70-1.29    2.31-2.58 -> 1.84-2.38    1.48-1.88 -> 1.09-1.25

The send's break-even is near 256 KiB and what it wins at 512 KiB is
small there (the cells win more: `rb4m_write` 0.25 ms of `sendmsg` and
0.1 of crc a large frame), within a factor of two of the receive's line,
so one `LINE` serves both. One thread that both sends and receives
4 MiB bodies is the limit of that pattern (3.0 ms of its CPU a body, the
wall 1.9-2.2 -> 3.3-3.7 ms a body; with two threads 2.15 -> 1.99).
"""
from __future__ import annotations

import atexit
import ctypes
import itertools
import os

from ceph_tpu.native import frame_native

#: a body to receive, or a frame's payload to send, of this many bytes or
#: more is the worker's (the tables above): an EC sub-op of a 4 MiB
#: object at k=8 is 512 KiB and its frame's body a little more
LINE = 512 << 10
#: threads. One was 69% busy in `rb4m_write` at 60 ops/s x 9 MiB each way
#: (`msgr_rx_worker_busy_pct` 40.7 + `msgr_tx_worker_busy_pct` 28.6; my
#: chip run, PR 54, call 1), past the 60 from which a second pays: with
#: two, 45.4 + 29.6 over both and `ops_s` 59.4-60.6 -> 62.5-62.9
WORKERS = 2

_FIELDS = 6             # u64s a completion, `rxw_reap`
_REAP_MAX = 64
#: a completion's status besides an errno: the body is whole; the peer
#: closed; (ours) the job was taken back because the connection was lost
WHOLE, EOF, LOST = 0, -1, -2

_lib = None             # the library; its calls let the interpreter's lock go
_held = None            # the same, through calls that keep it: `submit` and
#                         `_reap` are a mutex and a system call or two, and a
#                         loop that lets the lock go there waits to get it back
_checked = False
_tokens = itertools.count(1)
_jobs: dict[int, "Job"] = {}        # what the thread may be working on
_ports: dict = {}                   # event loop -> its _Port


def available() -> bool:
    """True when the native library loads and has the worker. Never
    raises."""
    global _lib, _held, _checked
    if not _checked:
        _checked = True
        try:
            from ceph_tpu import native
            lib = native.load()
        except Exception:
            return False
        if hasattr(lib, "rxw_submit") and hasattr(os, "eventfd"):
            _held = ctypes.PyDLL(lib._name)
            native.declare_rxw(_held)
            _lib = lib
            os.register_at_fork(after_in_child=_after_fork_child)
            atexit.register(_stop)
    return _lib is not None


class Job:
    """One body in the worker's hands. `fut` resolves to `(got, recvs,
    cpu_ns, bad, status)`: bytes of the body that are there, recv calls
    that brought some, the thread's CPU time on it, the first segment
    whose crc mismatched or -1, and `WHOLE`, `EOF`, `LOST` or an errno."""
    __slots__ = ("token", "buf", "have", "keep", "fut", "port", "handed_back",
                 "payload")


class _Port:
    """One event loop's eventfd, which the thread writes when a
    completion of a job submitted from this loop is in the ring."""

    def __init__(self, loop):
        self.loop = loop
        self.users = 0
        self.efd = os.eventfd(0, os.EFD_NONBLOCK | os.EFD_CLOEXEC)
        self._out = (ctypes.c_int64 * (_FIELDS * _REAP_MAX))()
        loop.add_reader(self.efd, self._reap)

    def _reap(self) -> None:
        try:
            os.eventfd_read(self.efd)
        except BlockingIOError:
            pass
        out = self._out
        n = _REAP_MAX
        while n == _REAP_MAX:
            n = _held.rxw_reap(out, _REAP_MAX)
            for i in range(0, n * _FIELDS, _FIELDS):
                job = _jobs.pop(out[i], None)
                if job is None:
                    continue            # cancelled once it was complete
                result = tuple(out[i + 1:i + _FIELDS])
                if job.port is self:
                    _resolve(job, result)
                else:                   # another loop's, in its thread
                    job.port.loop.call_soon_threadsafe(_resolve, job, result)

    def close(self) -> None:
        # a loop that is closed resolves no future and resumes no
        # transport: its jobs are only taken from the thread
        take = _drop if self.loop.is_closed() else cancel
        for job in [j for j in _jobs.values() if j.port is self]:
            take(job)
        self.loop.remove_reader(self.efd)
        os.close(self.efd)


def _resolve(job: Job, result: tuple) -> None:
    job.handed_back()
    if not job.fut.done():
        job.fut.set_result(result)


def acquire(loop) -> _Port:
    """The loop's port, for an endpoint that keeps it until `release`;
    the thread is started if it does not run."""
    port = _ports.get(loop)
    if port is None:
        for other in [p for p in _ports.values() if p.loop.is_closed()]:
            _retire(other)              # a loop that died with endpoints
        err = _lib.rxw_start(WORKERS)
        if err:
            raise OSError(-err, os.strerror(-err))
        port = _ports[loop] = _Port(loop)
    port.users += 1
    return port


def release(port: _Port) -> None:
    """An endpoint that acquired `port` is lost. The last one of a loop
    closes the loop's eventfd, the last one of all stops the thread."""
    port.users -= 1
    if port.users == 0 and _ports.get(port.loop) is port:
        _retire(port)


def _retire(port: _Port) -> None:
    del _ports[port.loop]
    port.close()
    if not _ports:
        _lib.rxw_stop()


def _enter(port: _Port, handed_back) -> Job:
    """A job of `port`'s loop, in `_jobs` from before the thread can
    have it."""
    job = Job()
    job.token = next(_tokens)
    job.handed_back = handed_back
    job.fut = port.loop.create_future()
    job.port = port
    _jobs[job.token] = job
    return job


def _entered(job: Job, err: int) -> Job:
    if err:
        del _jobs[job.token]
        raise OSError(-err, os.strerror(-err))
    return job


def submit(port: _Port, fd: int, buf: bytearray, have: int,
           seg_lens, handed_back) -> Job:
    """Hand the thread socket `fd` until `buf` is full past its first
    `have` bytes. `seg_lens`: the frame's segments, each followed by its
    crc in `buf`, for the thread to verify; None or empty for none.
    `handed_back()` is called on the job's loop when the socket is the
    caller's again, before the future resolves."""
    nseg = len(seg_lens) if seg_lens else 0
    job = _enter(port, handed_back)
    job.buf, job.have = buf, have
    job.keep = ctypes.c_char.from_buffer(buf)   # pins `buf`: no resize
    return _entered(job, _held.rxw_submit(
        job.token, fd, ctypes.addressof(job.keep), have, len(buf),
        (ctypes.c_uint64 * nseg)(*seg_lens) if nseg else None, nseg,
        port.efd))


def submit_tx(port: _Port, fd: int, pin: int, head: bytes, magic: int,
              tag: int, segments: list, handed_back) -> Job:
    """Hand the thread one frame to send on `fd`: the caller's dup of the
    connection's socket `pin`, which it keeps open until no job on it is
    the thread's. The frame is `Frame(tag, segments)` in plain-crc mode;
    `head` (may be empty) leaves in front of it. The job holds `head`,
    the header buffer and every part of every segment until it is reaped
    or cancelled; `handed_back()` is called on the job's loop when it is,
    before the future resolves to `(sent, sendmsgs, cpu_ns, -1, status)`."""
    nseg = len(segments)
    seg_parts, ptrs, lens, payload, keep = frame_native._flatten(segments)
    hdr = bytearray(8 + 8 * nseg)   # the preamble, then 4 bytes of crc each
    job = _enter(port, handed_back)
    # `have`: the bytes in front of the payload; a cancel that finds the
    # job finished and not reaped reports these alone as sent
    job.buf, job.have, job.payload = None, len(head) + 8 + 4 * nseg, payload
    job.keep = (head, hdr, keep, segments)
    return _entered(job, _held.rxw_submit_tx(
        job.token, fd, pin, port.efd, head, len(head), magic, tag, nseg,
        seg_parts, ptrs, lens,
        ctypes.addressof(ctypes.c_char.from_buffer(hdr))))


def _drop(job: Job) -> int | None:
    """Take `job` from the thread; returns when the thread touches
    neither its fd nor its buffer: the bytes that are there, -1 where the
    thread had finished it already, None where it was reaped or dropped
    before."""
    if _jobs.pop(job.token, None) is None:
        return None
    return _lib.rxw_cancel(job.token)


def cancel(job: Job) -> int | None:
    """Take `job` back, as `_drop` does, and resolve its future as
    `LOST`: the bytes of the body that are there (those it was submitted
    with, where the thread's completion is dropped unread); None, and
    nothing done, on a job that was reaped or cancelled."""
    got = _drop(job)
    if got is None:
        return None
    if got < 0:
        got = job.have
    _resolve(job, (got, 0, 0, -1, LOST))
    return got


def progress(job: Job) -> int:
    """Bytes of `job`'s body that are there; -1 once it is the thread's
    no longer."""
    return _held.rxw_progress(job.token)


def running() -> bool:
    return _lib is not None and bool(_lib.rxw_running())


def _stop() -> None:
    for port in list(_ports.values()):
        _retire(port)
    _lib.rxw_stop()


def _after_fork_child() -> None:
    # the thread is the parent's: the child drops its copies of the fds
    # and starts a thread of its own at its first large body
    _lib.rxw_forked()
    for port in _ports.values():
        os.close(port.efd)
    _ports.clear()
    _jobs.clear()
