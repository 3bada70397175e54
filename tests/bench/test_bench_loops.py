"""The loops against a fake server on a fake clock: the open loop times
each request from when it was due, however late the client submits it,
and the closed loop keeps its requests outstanding."""
import types

import numpy as np
import pytest

from harness import cells, e2e, gen
from harness.client import Client

CLASSES = [{"op": "eigh", "dims": [8, 9], "data": "symmetric"}]


class FakeClock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t


class FakeTicket:
    def __init__(self):
        self.done = False
        self.inflight = False
        self.record = None


class FakeServer:
    """Holds each flush for ``flush_s`` of fake time; flushes a request
    when ``max_delay_s`` has passed since its submit."""
    max_delay_s = 0.010

    def __init__(self, clock, flush_s):
        self.clock = clock
        self.flush_s = flush_s
        self.queue = []

    def submit(self, matrix, op):
        t = FakeTicket()
        self.queue.append((self.clock(), t))
        return t

    def poll(self):
        due = [(ts, t) for ts, t in self.queue
               if ts + self.max_delay_s <= self.clock()]
        if due:
            self.clock.t += self.flush_s          # a synchronous flush
            for ts, t in due:
                t.done = True
                t.record = types.SimpleNamespace(t_done=self.clock(),
                                                 t_dispatch=ts)
                self.queue.remove((ts, t))
        return len(due)

    def drain(self):
        for ts, t in list(self.queue):
            t.done = True
            t.record = types.SimpleNamespace(t_done=self.clock(),
                                             t_dispatch=ts)
        self.queue.clear()


@pytest.fixture
def fake(monkeypatch):
    clock = FakeClock()
    monkeypatch.setattr("time.sleep", lambda dt: setattr(
        clock, "t", clock.t + dt))
    return clock


def test_open_loop_latency_runs_from_due_time(fake):
    srv = FakeServer(fake, flush_s=0.050)   # slower than the arrivals
    client = Client(srv, gen.RequestStream(CLASSES, 1))
    loop = cells.load_module("loops", "open")
    traffic = {"arrivals": "poisson", "rate": 100.0}
    t_end = loop.run(client, traffic, 1.0, 3)
    w = e2e.Window(client.t_start, t_end, client.sent)
    due = gen.schedule(traffic, 1.0, 3)
    assert len(w.sent) == len(due)
    np.testing.assert_allclose([s.due - w.t_start for s in w.sent], due)
    late = [s.t_submit - s.due for s in w.sent]
    assert max(late) > 0.02                 # the flushes held the client
    for s in w.sent:
        assert s.latency_s == pytest.approx(s.t_done - s.due)
        assert s.latency_s >= s.t_done - s.t_submit
    assert e2e.p99_ms(w) >= 50.0


def test_closed_loop_keeps_requests_outstanding(fake):
    srv = FakeServer(fake, flush_s=0.005)
    client = Client(srv, gen.RequestStream(CLASSES, 2))
    loop = cells.load_module("loops", "closed")
    t_end = loop.run(client, {"outstanding": 8, "wait": "poll"}, 0.5, 0)
    w = e2e.Window(client.t_start, t_end, client.sent)
    assert all(s.ticket.done for s in w.sent)
    # every fulfilled request was replaced until the window closed
    inside = [s for s in w.sent if s.t_submit < t_end]
    assert len(inside) == len(w.sent)
    assert e2e.served_rps(w) == pytest.approx(
        sum(s.t_done <= t_end for s in w.sent) / 0.5)


def test_fit_s_counts_all_time_to_the_last_fit():
    sent = [types.SimpleNamespace(t_done=t) for t in (2.0, 4.0, 6.5)]
    w = e2e.Window(0.0, 5.0, sent)
    assert e2e.fit_s(w) == pytest.approx(6.5 / 3)
