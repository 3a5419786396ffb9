"""Kernels: the ideal time of the window's calls of the fused residual
member (``res_conv``: conv, bias, residual add, ReLU) over the device
time of the Pallas ops that carry its stable name, in percent.

Each launch of ``b`` frames makes the calls the network's
``res_conv_calls(config, b)`` counts (``bench/networks/resnet.py``); a
call's ideal time is the larger of its operations at the bf16 peak and
its minimum bytes at the HBM bandwidth (``bench/flops.py``).  An op
carries the name when its HLO instruction (the text before `` = ``) is
named ``res_conv``; another kernel that merely reads a ``res_conv``
output does not count.  None where the network has no such calls or the
window ran no such op."""
from bench import trace

NAME = "res_conv"


def is_res_conv(e) -> bool:
    """A Pallas op whose own instruction is named ``res_conv``."""
    return trace.is_kernel(e) and NAME in e.name.split(" = ", 1)[0]


def read(ctx):
    calls = getattr(ctx.network, "res_conv_calls", None)
    if calls is None or not ctx.planes:
        return None
    lo, hi = ctx.window_ns
    kernel_ns = sum(min(e.end_ns, hi) - max(e.start_ns, lo)
                    for p in ctx.planes for e in trace.device_ops(
                        ctx.events, p)
                    if is_res_conv(e) and e.end_ns > lo and e.start_ns < hi)
    if kernel_ns <= 0:
        return None
    peak, bandwidth = ctx.peaks["bf16_flops"], ctx.peaks["hbm_bytes_per_s"]
    ideal_s = sum(sum(w.ideal_s(peak, bandwidth)
                      for w in calls(ctx.config, launch.batch))
                  for launch in ctx.record.launches)
    return 100.0 * ideal_s / (kernel_ns / 1e9)
