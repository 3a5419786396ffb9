"""Host path: mean over launches of the ``bench.launch`` span less the
part of it in which an op ran on any of the cell's chips, in ms (in a
cell that reports frames/s)."""
from bench import trace


def read(ctx):
    return trace.host_ms_per_launch(ctx) if ctx.planes else None
