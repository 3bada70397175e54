"""Stack time (ms): mean host time per flush of stacking the requests into
the padded slab, the batch filler included, from ``FlushRecord.stack_s``
over the window's flushes.  Source: the server's clock stamps around its
``serve.stack`` stage (program spans); nothing where the server keeps no
such stamp."""


def read(ctx):
    values = [getattr(f, "stack_s", None) for f in ctx["records"]["flushes"]]
    if not values or None in values:
        return None
    return 1e3 * sum(values) / len(values)
