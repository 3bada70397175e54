"""Open loop: requests arrive on a schedule drawn from the seed, whether or
not earlier ones have finished.

Each request is due at its arrival time; the client submits every request
that is due, sleeps to the next arrival or the earliest flush deadline of
the queued requests, whichever comes first, and polls the server.  When
the server holds the client (a synchronous flush), later arrivals are
submitted late: their latency still runs from when they were due, and
the lateness is reported.  After the last arrival the client keeps polling
until every request of the window is fulfilled.
"""
from __future__ import annotations

from harness.gen import schedule


def run(client, traffic, seconds: float, seed: int) -> float:
    due = schedule(traffic, seconds, seed)
    t_end = client.start(seconds)
    t0 = client.t_start
    i = 0
    while i < len(due) or client.queued():
        now = client.clock()
        while i < len(due) and t0 + due[i] <= now:
            client.submit(client.make(), due=t0 + float(due[i]))
            i += 1
        nxt = t0 + float(due[i]) if i < len(due) else float("inf")
        client.sleep_until(min(nxt, client.next_deadline()))
        if client.clock() >= client.next_deadline():
            client.poll()
    client.drain()
    return t_end
