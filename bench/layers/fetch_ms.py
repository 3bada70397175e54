"""Fetch time (ms): mean host time per flush of the single device-to-host
gather of its results, once the device is done, from
``FlushRecord.fetch_s`` over the window's flushes.  Source: the server's
clock stamps around its ``serve.fetch`` stage (program spans); nothing
where the server keeps no such stamp."""


def read(ctx):
    values = [getattr(f, "fetch_s", None) for f in ctx["records"]["flushes"]]
    if not values or None in values:
        return None
    return 1e3 * sum(values) / len(values)
