"""Padding waste (%) of the window's device slabs: 1 - (elements of the
requests' true shapes) / (padded batch x bucket elements), summed over
every flush of the window.  Source: the server's ``FlushRecord`` and
``RequestRecord`` (program counters)."""
import math


def read(ctx):
    recs = ctx["records"]
    slab = sum(f.padded_batch * math.prod(f.bucket) for f in recs["flushes"])
    true = sum(math.prod(r.shape) for r in recs["requests"])
    if slab <= 0:
        return None
    return 100.0 * (1.0 - true / slab)
