"""Device: the share of the traced window in which no op ran, averaged
over the cell's chips, in percent (in a cell that reports frames/s)."""
from bench import trace


def read(ctx):
    return trace.idle_share(ctx) if ctx.planes else None
