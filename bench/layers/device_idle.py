"""Device idle share (%) of the traced segment: 1 - (union of the
operation intervals on each chip) / (the traced window), averaged over
the cell's chips.  Source: the profiler's device trace."""


def read(ctx):
    tr = ctx["trace"]
    if tr is None or tr.window_s <= 0:
        return None
    return 100.0 * tr.idle_share
