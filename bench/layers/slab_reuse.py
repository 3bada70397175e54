"""Slab reuse (%): the share of the window's flushes whose requests were
staged into a host slab kept from an earlier flush, from
``FlushRecord.slab_reused``.  Source: the server's per-flush record of
its staging pool (a program counter); nothing where the server keeps no
such field."""


def read(ctx):
    values = [getattr(f, "slab_reused", None)
              for f in ctx["records"]["flushes"]]
    if not values or None in values:
        return None
    return 100.0 * sum(map(bool, values)) / len(values)
