"""loop-affinity positives: driving another object's loop handle with
non-threadsafe primitives (each flagged line is a bug whenever the
caller runs on another thread than the owner's loop)."""
import asyncio


class Submitter:
    def __init__(self, svc, conn):
        self.svc = svc
        self.conn = conn
        self._loop = asyncio.new_event_loop()

    def kick(self, fn):
        # BAD: the service lives on another thread's loop; call_soon
        # from this thread corrupts its ready queue
        self.svc._loop.call_soon(fn)                      # finding 1

    def spawn(self, coro, other):
        # BAD: create_task on a foreign object's loop attribute
        other.loop.create_task(coro)                      # finding 2

    def ok_self(self, fn):
        self._loop.call_soon(fn)        # fine: our own loop, our thread

    def ok_threadsafe(self, fn, coro):
        self.svc._loop.call_soon_threadsafe(fn)           # the seam
        asyncio.run_coroutine_threadsafe(coro, self.svc._loop)
