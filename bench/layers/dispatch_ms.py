"""Dispatch time (ms): mean host time per flush of the dispatch stage --
stack, pad, executable lookup and launch, with the host-to-device copy --
from ``FlushRecord.dispatch_s`` over the window's flushes.  Source: the
server's host clock stamps (program spans)."""


def read(ctx):
    flushes = ctx["records"]["flushes"]
    if not flushes:
        return None
    return 1e3 * sum(f.dispatch_s for f in flushes) / len(flushes)
