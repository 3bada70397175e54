"""Queue time (ms): mean over the window's requests of the time from the
moment a request was due to its flush's dispatch (server clock stamps;
the client's due times).  Source: program spans and the client."""


def read(ctx):
    sent = [s for s in ctx["window"].sent if s.ticket.done]
    if not sent:
        return None
    return 1e3 * sum(s.ticket.record.t_dispatch - s.due
                     for s in sent) / len(sent)
