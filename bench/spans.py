"""Host time by layer, read from the program's own spans.

The program writes its spans into the profiler's trace
(``repro.obs.trace.TRACER``), so they share the device planes' clock.  A
layer's time is the self time of its spans in which no op ran on any of
the cell's chips, summed over the window and divided by the window's
``bench.launch`` spans, in ms.

Self time is taken on the thread that holds ``bench.window``, over the
spans of the launch path and ``bench.launch`` only.  So JAX's own events
beneath a program span (``PjitFunction_*``, ``DevicePut``), and the
program's cold-path spans (``replan``, ``select``), count to the
launch-path span that holds them.

A trace without the program's spans (a program that does not write
them) reads ``None``, not zero.
"""
from __future__ import annotations

import collections
from typing import Dict, Iterable, Optional

from bench import trace

LAYERS = {
    "scheduler": ("sched.admit", "sched.launch", "sched.judge"),
    "planner": ("arbiter.split", "serve.plan"),
    "dispatch": ("serve.stack", "serve.dispatch", "serve.results",
                 "serve.execute"),
}
# ``sched.block`` is the wait for the device: in no layer, but its own
# self time must not count to ``sched.launch``.
LAUNCH_PATH = frozenset(
    [n for names in LAYERS.values() for n in names] + ["sched.block"])
# The span every launch of a program that writes spans has.
WITNESS = "sched.launch"
JIT_TRACE = "jit.trace"


def _in_window(e: trace.Event, lo: float, hi: float) -> bool:
    return e.end_ns > lo and e.start_ns < hi


def self_idle_ns(ctx) -> Optional[Dict[str, float]]:
    """Span name -> ns of its self time in the window in which no op ran
    on any of the cell's chips; ``bench.launch`` holds what no program
    span covers.  None without the program's spans."""
    window = trace.spans(ctx.events, "bench.window")
    if not window:
        return None
    line = (window[0].plane, window[0].line)
    lo, hi = ctx.window_ns
    names = LAUNCH_PATH | {"bench.launch"}
    mine = [e for e in ctx.events if e.name in names
            and (e.plane, e.line) == line and _in_window(e, lo, hi)]
    if not any(e.name == WITNESS for e in mine):
        return None
    merged = trace.union(iv for p in ctx.planes for iv in ctx.busy(p))
    out: Dict[str, float] = collections.defaultdict(float)
    for s, e, name in trace.innermost(mine):
        s, e = max(s, lo), min(e, hi)
        if e > s:
            out[name] += (e - s) - trace.overlap(s, e, merged)
    return dict(out)


def launches(ctx) -> int:
    """``bench.launch`` spans wholly inside the window."""
    lo, hi = ctx.window_ns
    return sum(1 for s in trace.spans(ctx.events, "bench.launch")
               if s.start_ns >= lo and s.end_ns <= hi)


def idle_ms_per_launch(ctx, names: Iterable[str]) -> Optional[float]:
    """Device-idle self time of the spans ``names`` per launch, in ms;
    None without a device plane, a launch or the program's spans."""
    if not ctx.planes:
        return None
    by_name = self_idle_ns(ctx)
    n = launches(ctx)
    if by_name is None or not n:
        return None
    return sum(by_name.get(name, 0.0) for name in names) / n / 1e6


def layer_ms_per_launch(ctx, layer: str) -> Optional[float]:
    return idle_ms_per_launch(ctx, LAYERS[layer])


def jit_traces_in_window(ctx) -> Optional[int]:
    """``jit.trace`` instants (one per JAX tracing) in the window, on any
    host thread; None without a device plane or the program's spans."""
    if not ctx.planes:
        return None
    lo, hi = ctx.window_ns
    host = [e for e in ctx.events
            if not e.plane.startswith(trace.DEVICE_PREFIX)
            and e.name in (WITNESS, JIT_TRACE) and _in_window(e, lo, hi)]
    if not any(e.name == WITNESS for e in host):
        return None
    return sum(1 for e in host if e.name == JIT_TRACE)
