"""Kernels: the ideal time of the window's kernel calls over the device
time of every Pallas kernel in the window, in percent.

Each launch of ``b`` frames makes the calls that the network's
``calls(config, b)`` counts (``bench/networks/<network>.py``); for the
chain, one conv -> pool -> activation block call a block.  A call's
ideal time is the larger of its operations at the bf16 peak and its
minimum bytes at the HBM bandwidth (``bench/flops.py``).  An f32 dot at
``Precision.HIGHEST`` takes six bf16 passes, so an f32 kernel tops out
near a sixth of the compute bound."""
from bench import trace


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
    peak, bandwidth = ctx.peaks["bf16_flops"], ctx.peaks["hbm_bytes_per_s"]
    ideal_s = sum(sum(w.ideal_s(peak, bandwidth)
                      for w in ctx.network.calls(ctx.config, launch.batch))
                  for launch in ctx.record.launches)
    return 100.0 * ideal_s / (kernel_ns / 1e9)
