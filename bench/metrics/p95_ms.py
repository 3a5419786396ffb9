"""95th percentile latency of every frame completed in the window, from
its due time to the stamp after ``block_until_ready``."""
from bench.traffic import percentile


def read(ctx):
    lat = [f.done - f.due for f in ctx.record.ok()]
    return percentile(lat, 95) * 1e3 if lat else None
