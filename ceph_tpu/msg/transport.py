"""The messenger's socket endpoint: an `asyncio.BufferedProtocol` that
lets the kernel write a frame's body straight into the buffer the frame
keeps.

A read of `n` bytes goes one of three ways, chosen from `n` against the
spill's size and the worker's line, and from nothing else:

  * small (`n <= SPILL_SIZE`: the preamble, lengths and crc, ACK and
    keepalive frames, the handshake, control messages and small ops)
    is served from one fixed spill buffer per connection. Nothing is
    allocated but the bytes handed back.
  * large (a data segment's body) allocates the destination once, at
    its length, copies across whatever head of it already sits in the
    spill, and from then on `get_buffer` hands the kernel the unfilled
    tail: one `recv_into` takes whatever the socket holds, up to the
    whole rest of the body. The destination is a `bytearray` made at
    its length and not zero-filled (`_new_body`): every byte of it is
    the kernel's to write before anyone reads one, so no byte is
    written twice, and its pages are entered as the kernel's copy
    reaches them. The buffer is never resized, pooled or
    reused; it lives as long as a view of it does. A body that carries
    a write is kept for good by the store it is written to (MemStore
    adopts the read-only view it is handed, `objectstore/memstore.py`),
    so a recycling body pool, if one is ever built, is for the read
    direction only: bodies of replies, which die with their message.
  * larger still (`n >= rxworker.LINE`: a 512 KiB sub-op, a 4 MiB op or
    reply) gets the same destination and the same copy of the spill's
    head, and then the loop does not receive it at all: the endpoint
    pauses its transport and hands the socket, for exactly the rest of
    the body, to the receive worker (`msg/rxworker.py`), a native
    thread that `recv`s into the unfilled tail, checks the segments'
    crcs as the bytes arrive (`read_body` says so to `Frame.read`,
    which then checks none a second time) and wakes the loop once,
    when the body is whole: the kernel's copy, the first touch of the
    pages and the crc pass cost the loop nothing. Reading stays paused
    from before the submit until the completion is reaped or the job
    is taken back (`close`, a lost connection, a cancelled read), so a
    body is never read by both, and the thread never reads past the
    body's end, so the next preamble is the spill's. Where the native
    library is missing the second way serves these bodies too.

What a recv lands in the spill in front of a large body is copied a
second time, so how much of the spill the kernel is offered follows
the traffic: on a new connection and after a large body only `NARROW`
bytes, enough for the next preamble and little of what follows it;
once small reads have taken that much with no large one between them,
all of it, so that a run of small frames comes in one recv as it did
through a stream reader. Reading is paused only when the spill is full
of unread bytes, or for the worker; never in the middle of a body that
the transport itself receives.

The write side draws the same two lines. Under `rxworker.LINE` it is
the transport's own queue with `drain()` on its high-water mark, as
asyncio's stream writer had it. `writelines` puts a frame's parts there
by reference and the transport sends them with one scatter `sendmsg`;
what a partial send leaves stays views of the same objects, and the
queue keeps them alive until the kernel has the last byte. The
messenger's write loop sends a frame that way when its payload is
`SPILL_SIZE` or more, the same line the read side draws between the
spill and a body of its own, and as one packed blob below it. A
plain-crc frame of `rxworker.LINE` or more the loop does not send at
all: `send_frame` hands its segments, and the small blobs the same
wake-up framed in front of it, to the worker (`msg/rxworker.py`), whose
thread computes the segments' crcs, `sendmsg`s from where the parts lie
on a dup of the socket that the endpoint keeps for the connection's
life, waits for `EPOLLOUT` itself where the socket is full, and wakes
the loop once, when the kernel has the last byte (`frame_sent`, where
the write loop awaits `drain()` on the other path). The job, not the
transport's queue, keeps every part alive until it is reaped or taken
back. A frame is handed over only while the transport's own queue is
empty, and nothing is written to the transport while a job is out (the
write loop is the one writer and it is waiting), so the bytes of two
owners never interleave; where the queue is not empty or the submit
fails the frame goes the transport's way (`tx_worker_declined`). A job
is taken back by `close`, a lost connection or a cancelled write loop
before the fault is raised; a frame it leaves cut short on the wire
takes the transport with it (`abort`), so no byte follows it. Where the
native library is missing `writelines` sends these frames too. One
object is both ends: the messenger keeps it as reader and writer.
"""
from __future__ import annotations

import asyncio
import functools
import os

from ceph_tpu.msg import rxworker
from ceph_tpu.utils import tracer

#: bytes of spill per connection, and the size above which a read gets
#: a buffer of its own
SPILL_SIZE = 65536
#: the part of it offered between large bodies: a 512 KiB sub-op whose
#: head arrives with its preamble has at most 0.8% of itself copied
NARROW = SPILL_SIZE // 16


def _unzeroed():
    """`make(n)` -> a `bytearray` of length `n` whose bytes are not
    initialised (the C API's `PyByteArray_FromStringAndSize(NULL, n)`),
    or `bytearray` itself on an interpreter that has no `ctypes`, no
    `pythonapi` or no such symbol."""
    try:
        import ctypes
        # PYFUNCTYPE: the GIL stays held, as by the memset this replaces
        make = functools.partial(ctypes.PYFUNCTYPE(
            ctypes.py_object, ctypes.c_void_p, ctypes.c_ssize_t)(
            ("PyByteArray_FromStringAndSize", ctypes.pythonapi)), None)
        tried = make(8)
        if type(tried) is not bytearray or len(tried) != 8:
            return bytearray
    except (ImportError, AttributeError, OSError, TypeError, ValueError):
        return bytearray
    return make


#: the destination of a large read, `_new_body(n)`: what it holds is
#: stale heap until the kernel has written it
_new_body = _unzeroed()


class Endpoint(asyncio.BufferedProtocol):
    """One TCP transport's two ends. Read side: `await readexactly(n)`.
    Write side: `write`, `writelines`, `drain`, `close`, `wait_closed`,
    `get_extra_info`, `.transport`."""

    def __init__(self, perf, on_connect=None):
        self._perf = perf
        self._on_connect = on_connect       # acceptor: called once made
        self.transport: asyncio.Transport | None = None
        self._loop: asyncio.AbstractEventLoop | None = None
        # the endpoint's own window on its own spill, the one buffer
        # here that is reused: nothing leaves it but bytes() copies
        # radoslint: disable-next=view-escape
        self._spill_mv = memoryview(bytearray(SPILL_SIZE))
        self._rpos = 0              # spill[_rpos:_wpos] is unread
        self._wpos = 0
        self._dest: memoryview | None = None    # body being filled
        self._dest_pos = 0
        self._fd = -1               # the socket, where the worker can have it
        self._port = None           # the worker's, once a body went there
        self._job: rxworker.Job | None = None   # body in the worker's hands
        self._tx_fd = -1            # a dup the worker sends on, once it has
        self._tx_job: rxworker.Job | None = None    # frame in its hands
        self._need = 0              # spill bytes the parked read wants
        self._small_run = 0         # bytes of small reads since a body
        self._read_waiter: asyncio.Future | None = None
        self._reading_paused = False
        self._eof = False
        self._exc: BaseException | None = None
        self._lost = False
        self._writing_paused = False
        self._drain_waiters: list[asyncio.Future] = []
        self._closed: asyncio.Future | None = None

    # -- protocol callbacks --------------------------------------------------

    def connection_made(self, transport) -> None:
        self.transport = transport
        self._loop = asyncio.get_running_loop()
        self._closed = self._loop.create_future()
        sock = transport.get_extra_info("socket")
        if sock is not None:
            self._fd = sock.fileno()
        if self._on_connect is not None:
            self._on_connect(self)

    def get_buffer(self, sizehint: int) -> memoryview:
        dest = self._dest
        if dest is not None:
            return dest[self._dest_pos:]
        if self._wpos == SPILL_SIZE:
            # the head is consumed: a spill full of unread bytes has
            # paused reading and is not asked
            self._compact()
        end = SPILL_SIZE
        if self._small_run < NARROW:
            end = self._rpos + max(NARROW, self._need)
        if not self._wpos < end <= SPILL_SIZE:
            # a window already full (its reader woken, and not here
            # yet) or past the end gives way to the room there is
            end = SPILL_SIZE
        return self._spill_mv[self._wpos:end]

    def buffer_updated(self, nbytes: int) -> None:
        perf = self._perf
        perf.inc("rx_recvs")
        dest = self._dest
        if dest is not None:
            perf.inc("rx_direct_bytes", nbytes)
            self._dest_pos += nbytes
            if self._dest_pos == len(dest):
                # back to the spill before the next get_buffer: an
                # empty view would be fatal to the transport
                self._dest = None
                self._wake_reader()
            return
        perf.inc("rx_spill_bytes", nbytes)
        self._wpos += nbytes
        have = self._wpos - self._rpos
        if have >= self._need:
            self._wake_reader()
        if have == SPILL_SIZE:
            # full, and whoever reads has all it asked for: no recv
            # until it is back for more
            self._reading_paused = True
            self.transport.pause_reading()

    def eof_received(self) -> bool:
        self._eof = True
        self._wake_reader()
        return True     # the write side stays ours to close

    def connection_lost(self, exc) -> None:
        self._lost = True
        self._eof = True
        if exc is not None and self._exc is None:
            self._exc = exc
        self._fd = -1       # asyncio closes it when this returns
        self._take_back()
        self._take_back_tx()
        if self._tx_fd >= 0:
            os.close(self._tx_fd)
            self._tx_fd = -1
        if self._port is not None:
            port, self._port = self._port, None
            rxworker.release(port)
        self._wake_reader()
        self._wake_drainers()
        if not self._closed.done():
            self._closed.set_result(None)

    def pause_writing(self) -> None:
        self._writing_paused = True

    def resume_writing(self) -> None:
        self._writing_paused = False
        self._wake_drainers()

    def _wake_drainers(self) -> None:
        for w in self._drain_waiters:
            if not w.done():
                w.set_result(None)
        self._drain_waiters.clear()

    # -- read side -----------------------------------------------------------

    def _compact(self) -> None:
        have = self._wpos - self._rpos
        self._spill_mv[:have] = self._spill_mv[self._rpos:self._wpos]
        self._rpos, self._wpos = 0, have

    def _wake_reader(self) -> None:
        w = self._read_waiter
        if w is not None:
            self._read_waiter = None
            if not w.done():
                w.set_result(None)

    async def _wait_for_data(self) -> None:
        if self._read_waiter is not None:
            raise RuntimeError("readexactly() called while another "
                               "read waits on this endpoint")
        if self._reading_paused:
            self._reading_paused = False
            self.transport.resume_reading()
        self._read_waiter = self._loop.create_future()
        try:
            await self._read_waiter
        finally:
            self._read_waiter = None

    def _read_failed(self, partial: bytes, n: int) -> BaseException:
        if self._exc is not None:
            return self._exc
        return asyncio.IncompleteReadError(partial, n)

    async def readexactly(self, n: int) -> bytes | bytearray:
        """Exactly `n` bytes: `bytes` out of the spill for a small read,
        a fresh `bytearray` the kernel filled for a large one. EOF or a
        lost connection short of `n` raises as asyncio's streams do."""
        if n > SPILL_SIZE:
            return (await self._read_body(n, None))[0]
        if self._rpos + n > SPILL_SIZE:
            self._compact()
        self._need = n
        while self._wpos - self._rpos < n:
            if self._eof:
                raise self._read_failed(
                    bytes(self._spill_mv[self._rpos:self._wpos]), n)
            await self._wait_for_data()
        if self._small_run < NARROW:
            self._small_run += n
        start = self._rpos
        out = bytes(self._spill_mv[start:start + n])
        if start + n == self._wpos:
            self._rpos = self._wpos = 0
        else:
            self._rpos = start + n
        return out

    async def read_body(self, n: int, seg_lens: list[int]) -> tuple:
        """A frame's body of `n` bytes, `seg_lens[i]` bytes and a crc32c
        a segment: `(body, bad)` with `bad` None where nobody has
        checked the crcs yet (the caller does), else -1 or the first
        segment that failed (the worker checked them as it received)."""
        if n > SPILL_SIZE:
            return await self._read_body(n, seg_lens)
        return await self.readexactly(n), None

    async def _read_body(self, n: int, seg_lens) -> tuple:
        # `buf` is not zero-filled: until `_dest_pos == n` its tail is
        # stale heap (earlier messages, keys), so neither it nor `_dest`
        # is ever read, logged or dumped past `_dest_pos`
        with tracer.section("msgr.rx_alloc"):
            buf = _new_body(n)
        have = self._wpos - self._rpos      # < n: the spill is smaller
        if have:
            buf[:have] = self._spill_mv[self._rpos:self._wpos]
            self._rpos = self._wpos = 0
        self._small_run = 0
        if n >= rxworker.LINE and self._fd >= 0 and not self._eof \
                and rxworker.available():
            job = self._hand_over(buf, have, seg_lens)
            if job is not None:
                return await self._worker_body(job, bool(seg_lens))
        self._dest_pos = have
        # the kernel's window on `buf` while it fills, dropped when it
        # is full; `buf` itself is never reused
        # radoslint: disable-next=view-escape
        self._dest = memoryview(buf)
        try:
            while self._dest is not None:
                if self._eof:
                    raise self._read_failed(
                        bytes(buf[:self._dest_pos]), n)
                await self._wait_for_data()
        finally:
            # a cancelled or failed read gives the socket back to the
            # spill; the bytes it had taken are lost with the transport
            self._dest = None
        return buf, None

    def body_filled(self) -> int:
        """Bytes there of the body being received, whoever receives it
        (the transport or the receive worker); -1 between bodies."""
        if self._job is not None:
            return rxworker.progress(self._job)
        return self._dest_pos if self._dest is not None else -1

    def _hand_over(self, buf: bytearray, have: int, seg_lens):
        """Give the socket to the receive worker for the rest of `buf`:
        the transport does not read from before the submit until the
        job is reaped or taken back, so a body is never read by both.
        None where the worker cannot have it (no thread, no fd to
        spare): the read goes on here."""
        try:
            if self._port is None:
                self._port = rxworker.acquire(self._loop)
            if not self._reading_paused:
                self._reading_paused = True
                self.transport.pause_reading()
            self._job = rxworker.submit(self._port, self._fd, buf, have,
                                        seg_lens, self._handed_back)
        except OSError:
            return None
        return self._job

    async def _worker_body(self, job, verifies: bool) -> tuple:
        buf = job.buf
        try:
            got, recvs, cpu_ns, bad, status = await job.fut
        finally:
            # a cancelled read: the bytes are lost with the transport
            self._take_back()
        if status == rxworker.LOST:     # `_take_back` has counted it
            raise self._read_failed(bytes(buf[:got]), len(buf))
        self._count_worker(got - job.have, recvs, cpu_ns)
        if status == rxworker.WHOLE:
            self._perf.inc("rx_worker_bodies")
            return buf, (bad if verifies else None)
        if status == rxworker.EOF:
            self._eof = True
            raise asyncio.IncompleteReadError(bytes(buf[:got]), len(buf))
        raise OSError(status, os.strerror(status))

    def _count_worker(self, nbytes: int, recvs: int, cpu_ns: int) -> None:
        perf = self._perf
        perf.inc("rx_recvs", recvs)
        perf.inc("rx_direct_bytes", nbytes)
        perf.inc("rx_worker_bytes", nbytes)
        perf.inc("rx_worker_cpu_ns", cpu_ns)

    def _handed_back(self) -> None:
        """The worker is done with the socket, whole body or not (called
        from the reap, or from a cancel): the transport reads again at
        once, and finds the EOF or the error the worker found, not a
        turn of the loop later when the reader is back for the next
        preamble."""
        self._job = None
        if self._reading_paused and not self._lost:
            self._reading_paused = False
            self.transport.resume_reading()

    def _take_back(self) -> None:
        """No job is the worker's when this returns (`close`, a lost
        connection, a cancelled read)."""
        job, self._job = self._job, None
        got = rxworker.cancel(job) if job is not None else None
        if got is not None:
            self._perf.inc("rx_worker_cancelled")
            self._count_worker(got - job.have, 0, 0)

    # -- write side ----------------------------------------------------------

    def worker_sends(self, nbytes: int) -> bool:
        """True where a plain-crc frame of `nbytes` of payload is the
        send worker's to send (`send_frame`) and not the transport's:
        from the line up, on a socket, with the native library."""
        return nbytes >= rxworker.LINE and self._fd >= 0 \
            and not self._lost and rxworker.available()

    def send_frame(self, head: list, frame) -> rxworker.Job | None:
        """Hand `frame` to the send worker, the packed blobs of `head`
        (what the same wake-up framed in front of it) leaving first in
        the same job: the thread computes the segments' crcs and
        `sendmsg`s from where the parts lie, and `frame_sent` awaits it.
        The job holds every part until it is reaped or taken back.
        None, and `tx_worker_declined` counted, where the transport's
        own queue still holds bytes (two owners' bytes would interleave)
        or the worker cannot have the frame (no thread, no fd to spare):
        the caller sends it through `writelines`."""
        if not (self.transport.is_closing()
                or self.transport.get_write_buffer_size()):
            try:
                if self._port is None:
                    self._port = rxworker.acquire(self._loop)
                if self._tx_fd < 0:
                    self._tx_fd = os.dup(self._fd)
                self._tx_job = rxworker.submit_tx(
                    self._port, self._tx_fd, self._fd, b"".join(head),
                    *frame.send_args(), self._sent_back)
                return self._tx_job
            except OSError:
                pass
        self._perf.inc("tx_worker_declined")
        return None

    async def frame_sent(self, job) -> None:
        """Wait until the kernel has the last byte of `job`'s frame: the
        worker's counterpart of `drain()`."""
        try:
            sent, sends, cpu_ns, _bad, status = await job.fut
        finally:
            # a cancelled write loop: the frame is cut short on the wire
            self._take_back_tx()
        if status == rxworker.LOST:     # `_take_back_tx` has counted it
            raise self._exc or ConnectionResetError("connection lost")
        self._count_tx_worker(job, sent, cpu_ns)
        if status != rxworker.WHOLE:
            # the thread met the fault before the transport did; the
            # frame is cut short on the wire and no byte may follow it
            if not self._lost:
                self.transport.abort()
            raise OSError(status, os.strerror(status))
        self._perf.inc("tx_worker_bodies")

    def _count_tx_worker(self, job, sent: int, cpu_ns: int) -> None:
        # of `sent`, what was payload: not what leaves in front of it
        # (`job.have`: the head and the preamble), nor the crcs
        self._perf.inc("tx_worker_bytes",
                       min(max(sent - job.have, 0), job.payload))
        self._perf.inc("tx_worker_cpu_ns", cpu_ns)

    def _sent_back(self) -> None:
        self._tx_job = None

    def _take_back_tx(self) -> None:
        """No frame is the send worker's when this returns (`close`, a
        lost connection, a cancelled write loop). One it had not
        finished is cut short on the wire: the transport is aborted, so
        that no byte follows it."""
        job, self._tx_job = self._tx_job, None
        sent = rxworker.cancel(job) if job is not None else None
        if sent is not None:
            self._perf.inc("tx_worker_cancelled")
            self._count_tx_worker(job, sent, 0)
            if not self._lost:
                self.transport.abort()

    def write(self, data) -> None:
        self.transport.write(data)

    def writelines(self, parts) -> None:
        """Queue `parts` for one scatter `sendmsg`, each by reference:
        the transport keeps a view of every part until the kernel has
        taken its last byte, and what a partial send leaves stays views
        of the same objects, never a joined copy. So the caller leaves
        the buffers unwritten until `drain()` has returned with the
        queue empty, or for good (`Frame.encode_parts` says who does).

        Nothing is queued on a transport that is closing or lost (the
        next `drain()` raises): asyncio's `writelines`, unlike its
        `write`, would queue there all the same and register the dead
        socket's number for writing, and the next socket to be given
        that number, this session's reconnect, would then never learn
        that it is connected."""
        if not self.transport.is_closing():
            self.transport.writelines(parts)

    async def drain(self) -> None:
        if not self._lost and self.transport.is_closing():
            # let connection_lost() run, so a write loop on a closing
            # transport faults instead of spinning
            await asyncio.sleep(0)
        if not self._lost and self._writing_paused:
            w = self._loop.create_future()
            self._drain_waiters.append(w)
            await w
        if self._lost:
            raise self._exc or ConnectionResetError("connection lost")

    def close(self) -> None:
        self._take_back()
        self._take_back_tx()
        self.transport.close()

    def __del__(self) -> None:
        # a loop that died with the endpoint open never called
        # `connection_lost`; the socket closes with its object, the
        # send dup is a bare number
        if self._tx_fd >= 0:
            try:
                os.close(self._tx_fd)
            except OSError:
                pass

    async def wait_closed(self) -> None:
        # shielded: a waiter that is cancelled must not cancel the
        # future the others wait on
        await asyncio.shield(self._closed)

    def get_extra_info(self, name: str, default=None):
        return self.transport.get_extra_info(name, default)
