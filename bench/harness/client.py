"""The benchmark's client: the one thread that makes every server call.

It drives ``PCAServer.submit`` -> ``Ticket`` with ``poll``/``drain``/
``Ticket.wait``, stamps each request with the moment it was due, and
wraps each of its own calls in a ``jax.profiler.TraceAnnotation`` so a
trace can say what the host was doing in a device idle gap.  Times are on
the server's clock (``time.monotonic`` unless the server was given
another).
"""
from __future__ import annotations

import collections
import dataclasses
import math
import queue
import threading
import time
from typing import Callable, Deque, List, Optional

import jax

from .gen import Request, RequestStream


@dataclasses.dataclass
class Sent:
    req: Request
    due: float
    t_submit: float
    ticket: object

    @property
    def t_done(self) -> float:
        return self.ticket.record.t_done

    @property
    def latency_s(self) -> float:
        return self.t_done - self.due


class Prefetcher:
    """Makes the next request on a helper thread while the client waits
    on the server (large requests take a while to copy).  The helper
    never calls the server."""

    def __init__(self, stream: RequestStream, depth: int):
        self._q: "queue.Queue" = queue.Queue(maxsize=depth)
        self._stream = stream
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._fill, daemon=True)
        self._thread.start()

    def _fill(self):
        while not self._stop.is_set():
            req = self._stream.next()
            while not self._stop.is_set():
                try:
                    self._q.put(req, timeout=0.1)
                    break
                except queue.Full:
                    continue

    def next(self) -> Request:
        return self._q.get()

    def close(self):
        self._stop.set()
        self._thread.join(timeout=30)
        if self._thread.is_alive():
            raise RuntimeError("request prefetch thread did not stop")


class Client:
    def __init__(self, srv, stream: RequestStream, prefetch: int = 0):
        self.srv = srv
        self.clock: Callable[[], float] = srv.clock
        self._stream = stream
        self._pre = Prefetcher(stream, prefetch) if prefetch else None
        self.sent: List[Sent] = []
        self._open: Deque[Sent] = collections.deque()
        self._ready: List[Request] = []
        self.t_start = math.nan

    def prime(self, n: int) -> None:
        """Make the first ``n`` requests now, before the window opens."""
        self._ready = [self.make() for _ in range(n)]

    def start(self, seconds: float) -> float:
        """Open the window now; returns its end."""
        self.t_start = self.clock()
        return self.t_start + seconds

    def queued(self) -> bool:
        """Whether any submitted request is still unfulfilled."""
        while self._open and self._open[0].ticket.done:
            self._open.popleft()
        return bool(self._open)

    def next_deadline(self) -> float:
        """Earliest flush deadline among requests still queued in the
        server (inf when none is)."""
        self.queued()
        for s in self._open:
            if not s.ticket.done and not s.ticket.inflight:
                return s.t_submit + self.srv.max_delay_s
        return math.inf

    def sleep_until(self, t: float) -> None:
        dt = t - self.clock()
        if math.isfinite(dt) and dt > 0:
            time.sleep(dt)

    def make(self) -> Request:
        if self._ready:
            return self._ready.pop(0)
        with jax.profiler.TraceAnnotation("bench.make_request"):
            if self._pre is not None:
                return self._pre.next()
            return self._stream.next()

    def submit(self, req: Request, due: Optional[float] = None) -> Sent:
        with jax.profiler.TraceAnnotation("bench.submit"):
            t = self.clock()
            ticket = self.srv.submit(req.matrix, op=req.op)
        s = Sent(req, t if due is None else due, t, ticket)
        self.sent.append(s)
        self._open.append(s)
        return s

    def poll(self) -> int:
        with jax.profiler.TraceAnnotation("bench.poll"):
            return self.srv.poll()

    def wait(self, s: Sent) -> None:
        with jax.profiler.TraceAnnotation("bench.wait"):
            s.ticket.wait()

    def drain(self) -> None:
        with jax.profiler.TraceAnnotation("bench.wait"):
            self.srv.drain()

    def close(self) -> None:
        if self._pre is not None:
            self._pre.close()
