"""Glue: the device time of the ops that are not Pallas kernels (the
pads, copies, adds and head ops XLA runs around the planned kernels)
over the device's busy time in the traced window, in percent, averaged
over the cell's chips."""
from bench import trace


def read(ctx):
    if not ctx.planes:
        return None
    lo, hi = ctx.window_ns
    shares = []
    for p in ctx.planes:
        busy = sum(e - s for s, e in ctx.busy(p))
        if busy <= 0:
            continue
        glue = sum(min(e.end_ns, hi) - max(e.start_ns, lo)
                   for e in trace.device_ops(ctx.events, p)
                   if not trace.is_kernel(e) and e.end_ns > lo
                   and e.start_ns < hi)
        shares.append(100.0 * glue / busy)
    return sum(shares) / len(shares) if shares else None
