"""Closed loop: ``outstanding`` requests in flight at all times.

Each fulfilled request is replaced at once by a new one, due the moment
it is submitted, until the window closes; then the server is drained.
``wait: "ticket"`` (one request outstanding, as a client that fits one
dataset at a time) blocks on the request's own ticket, which dispatches
it at once; ``wait: "poll"`` sleeps to the earliest flush deadline of the
queued requests and polls the server, as a client with many requests
queued does.
"""
from __future__ import annotations


def run(client, traffic, seconds: float, seed: int) -> float:
    """Drive the server for ``seconds``; returns the window's end (the
    window starts at the first submit, ``client.t_start``)."""
    del seed
    n = int(traffic["outstanding"])
    by_ticket = traffic.get("wait", "poll") == "ticket"
    if by_ticket and n != 1:
        raise ValueError("wait='ticket' serves one request at a time")
    t_end = client.start(seconds)
    live = [client.submit(client.make()) for _ in range(n)]
    while True:
        if by_ticket:
            client.wait(live[0])
        else:
            client.sleep_until(client.next_deadline())
            client.poll()
        now = client.clock()
        if now >= t_end:
            break
        live = [s if not s.ticket.done else client.submit(client.make())
                for s in live]
    client.drain()
    return t_end
