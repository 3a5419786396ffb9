"""Kernels: the ideal time of the window's 1x1 conv unit calls over the
device time of the Pallas ops that carry the flat 1x1 body's stable name
(``res_conv_1x1``), in percent.

Each launch of ``b`` frames makes one call per unit with a 1x1 kernel
among the network's ``units(config)`` (``bench/networks/resnet.py``),
with the work ``unit_work(b, ...)`` counts; a call's ideal time is the
larger of its operations at the bf16 peak and its minimum bytes at the
HBM bandwidth (``bench/flops.py``).  An op carries the name when its
HLO instruction (the text before `` = ``) is named ``res_conv_1x1``.
None where the network has no ``units`` or the window ran no such op,
as in a program that runs its 1x1 units through the row body."""
from bench import trace

NAME = "res_conv_1x1"


def is_flat(e) -> bool:
    """A Pallas op whose own instruction is named ``res_conv_1x1``."""
    return trace.is_kernel(e) and NAME in e.name.split(" = ", 1)[0]


def read(ctx):
    units = getattr(ctx.network, "units", None)
    if units is None or not ctx.planes:
        return None
    lo, hi = ctx.window_ns
    kernel_ns = sum(min(e.end_ns, hi) - max(e.start_ns, lo)
                    for p in ctx.planes for e in trace.device_ops(
                        ctx.events, p)
                    if is_flat(e) and e.end_ns > lo and e.start_ns < hi)
    if kernel_ns <= 0:
        return None
    peak, bandwidth = ctx.peaks["bf16_flops"], ctx.peaks["hbm_bytes_per_s"]
    ones = [u[1:] for u in units(ctx.config) if u[5] == 1]
    ideal_s = sum(ctx.network.unit_work(launch.batch, *u).ideal_s(
        peak, bandwidth) for launch in ctx.record.launches for u in ones)
    return 100.0 * ideal_s / (kernel_ns / 1e9)
