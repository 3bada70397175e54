"""Put time (ms): mean host time per flush of handing the slab and its
true sizes to the device (``jnp.asarray``), from ``FlushRecord.put_s``
over the window's flushes.  Source: the server's clock stamps around its
``serve.put`` stage (program spans); nothing where the server keeps no
such stamp."""


def read(ctx):
    values = [getattr(f, "put_s", None) for f in ctx["records"]["flushes"]]
    if not values or None in values:
        return None
    return 1e3 * sum(values) / len(values)
