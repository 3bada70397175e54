"""Unpack time (ms): mean host time per flush of unpacking its results into
the tickets, with their request records and fulfilment, from
``FlushRecord.unpack_s`` over the window's flushes.  Source: the server's
clock stamps around its ``serve.unpack`` stage (program spans); nothing
where the server keeps no such stamp."""


def read(ctx):
    values = [getattr(f, "unpack_s", None)
              for f in ctx["records"]["flushes"]]
    if not values or None in values:
        return None
    return 1e3 * sum(values) / len(values)
