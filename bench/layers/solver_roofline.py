"""Solver roofline share (%): the least time the traced segment's
requests need at the chip's published peaks, over the device time of the
solver executables (XLA modules) that served them.

The work counts each request's op and true shape only (``harness.work``):
no sweeps, no bucket padding, no batch filler; split over the cell's
chips.  Source: the device trace (module time) and the server's request
records (shapes)."""
from harness import peaks, work


def read(ctx):
    tr = ctx["trace"]
    if tr is None:
        return None
    device_s = sum(tr.modules.values())
    reqs = ctx["traced_records"]["requests"]
    if device_s <= 0 or not reqs:
        return None
    peak = peaks.peaks(ctx["devices"][0].device_kind)
    flops = nbytes = 0.0
    for r in reqs:
        f, b = work.request_work(r.op, r.shape)
        flops += f
        nbytes += b
    least, _bound = work.roofline_seconds(flops, nbytes, peak)
    return 100.0 * least / tr.chips / device_s
