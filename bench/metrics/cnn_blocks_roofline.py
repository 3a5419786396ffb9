"""Kernels: the ideal time of the window's conv -> pool -> activation
block calls over the device time of every Pallas kernel in the window,
in percent.

Each launch of ``b`` frames makes one call of every block on ``b``
frames.  A call's ideal time is the larger of its operations at the
bf16 peak and its minimum bytes at the HBM bandwidth
(``bench/flops.py``).  An f32 dot at ``Precision.HIGHEST`` takes six
bf16 passes, so an f32 kernel tops out near a sixth of the compute
bound."""
from bench import flops, trace


def read(ctx):
    if not ctx.planes:
        return None
    lo, hi = ctx.window_ns
    kernel_ns = sum(min(e.end_ns, hi) - max(e.start_ns, lo)
                    for p in ctx.planes for e in trace.device_ops(
                        ctx.events, p)
                    if trace.is_kernel(e) and e.end_ns > lo
                    and e.start_ns < hi)
    if kernel_ns <= 0:
        return None
    ideal_s = sum(flops.blocks_ideal_s(
        ctx.config, launch.batch, ctx.peaks["bf16_flops"],
        ctx.peaks["hbm_bytes_per_s"]) for launch in ctx.record.launches)
    return 100.0 * ideal_s / (kernel_ns / 1e9)
