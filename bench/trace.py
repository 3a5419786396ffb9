"""From the profiler's ``.xplane.pb`` to a plain event list, and from that
list to what the per-layer readers need.

``load_events`` reads the file with ``jax.profiler.ProfileData``.  The
rest works on plain ``Event`` tuples, so the tests build small lists by
hand.

A device plane is named ``/device:TPU:<n>``.  On it, the ops the chip
ran are the events of its ``XLA Ops`` line, each named by its HLO
instruction; a Pallas kernel is an op whose instruction is the
``custom-call`` to ``tpu_custom_call`` that a ``pallas_call`` lowers
to.  The benchmark's own spans (``jax.profiler.TraceAnnotation``) are
host events named ``bench.*``.
"""
from __future__ import annotations

import bisect
import collections
import glob
from typing import Dict, Iterable, List, NamedTuple, Sequence, Tuple

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"


class Event(NamedTuple):
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


def find_xplane(trace_dir) -> str:
    paths = sorted(glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def load_events(path) -> List[Event]:
    """Every event of the device planes' op lines and of the host
    planes, as plain tuples."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(str(path)).planes:
        device = plane.name.startswith(DEVICE_PREFIX)
        if not device and not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            if device and line.name != OPS_LINE:
                continue
            for ev in line.events:
                out.append(Event(plane.name, line.name, ev.name,
                                 float(ev.start_ns), float(ev.duration_ns)))
    return out


def device_planes(events: Iterable[Event]) -> List[str]:
    return sorted({e.plane for e in events
                   if e.plane.startswith(DEVICE_PREFIX)})


def device_ops(events: Iterable[Event], plane: str) -> List[Event]:
    return [e for e in events if e.plane == plane and e.line == OPS_LINE]


def is_kernel(e: Event) -> bool:
    """A Pallas kernel: a device op lowered from ``tpu_custom_call``."""
    return 'custom_call_target="tpu_custom_call"' in e.name


def union(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Merge (start, end) intervals into disjoint sorted ones."""
    merged: List[List[float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def overlap(lo: float, hi: float,
            merged: Sequence[Tuple[float, float]]) -> float:
    """Length of [lo, hi] covered by disjoint sorted ``merged``."""
    total = 0.0
    i = max(0, bisect.bisect_right(merged, (lo, float("inf"))) - 1)
    while i < len(merged) and merged[i][0] < hi:
        s, e = merged[i]
        if e > lo:
            total += min(e, hi) - max(s, lo)
        i += 1
    return total


def busy(events: Sequence[Event], plane: str, lo: float,
         hi: float) -> List[Tuple[float, float]]:
    """Disjoint intervals of [lo, hi] in which an op ran on ``plane``."""
    return union((max(e.start_ns, lo), min(e.end_ns, hi))
                 for e in device_ops(events, plane)
                 if e.end_ns > lo and e.start_ns < hi)


def spans(events: Iterable[Event], name: str) -> List[Event]:
    """Host events of one benchmark annotation, in time order."""
    return sorted((e for e in events
                   if not e.plane.startswith(DEVICE_PREFIX)
                   and e.name == name), key=lambda e: e.start_ns)


def gaps(merged: Sequence[Tuple[float, float]], lo: float,
         hi: float) -> List[Tuple[float, float]]:
    """The complement of ``merged`` in [lo, hi]."""
    out, t = [], lo
    for s, e in merged:
        if s > t:
            out.append((t, min(s, hi)))
        t = max(t, e)
    if t < hi:
        out.append((t, hi))
    return [(s, e) for s, e in out if e > s]


def top_ops(events: Sequence[Event], planes: Sequence[str], lo: float,
            hi: float, n: int = 10) -> List[list]:
    """[name, seconds] of the device ops that took most time in [lo, hi],
    summed over ``planes``."""
    total: Dict[str, float] = collections.defaultdict(float)
    for plane in planes:
        for e in device_ops(events, plane):
            d = min(e.end_ns, hi) - max(e.start_ns, lo)
            if d > 0:
                total[e.name] += d
    ranked = sorted(total.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns / 1e9] for name, ns in ranked]


def innermost(events: Iterable[Event]) -> List[Tuple[float, float, str]]:
    """Disjoint (start, end, name) segments of one thread's nested spans,
    each named after the innermost span open in it."""
    segs: List[Tuple[float, float, str]] = []
    stack: List[Event] = []
    t = None

    def advance(upto):
        nonlocal t
        while stack and stack[-1].end_ns <= upto:
            top = stack.pop()
            if top.end_ns > t:
                segs.append((t, top.end_ns, top.name))
                t = top.end_ns
        if stack and upto > t:
            segs.append((t, upto, stack[-1].name))
        t = max(t, upto)

    for e in sorted(events, key=lambda e: (e.start_ns, -e.dur_ns)):
        if t is None:
            t = e.start_ns
        advance(e.start_ns)
        stack.append(e)
    if stack:
        advance(max(e.end_ns for e in stack))
    return segs


def idle_by_host(events: Sequence[Event], plane: str, lo: float, hi: float,
                 n: int = 10) -> List[list]:
    """[host activity, seconds] of ``plane``'s idle time in [lo, hi].

    The host activity is the innermost span open on the thread that holds
    the ``bench.window`` span (the benchmark's and JAX's Python-level
    spans); idle time in which none is open is "host: untraced"."""
    window = spans(events, "bench.window")
    if not window:
        return []
    line = (window[0].plane, window[0].line)
    segs = innermost(e for e in events if (e.plane, e.line) == line
                     and e.name != "bench.window" and e.end_ns > lo
                     and e.start_ns < hi)
    total: Dict[str, float] = collections.defaultdict(float)
    starts = [s for s, _, _ in segs]
    for gs, ge in gaps(busy(events, plane, lo, hi), lo, hi):
        covered = 0.0
        i = max(0, bisect.bisect_right(starts, gs) - 1)
        while i < len(segs) and segs[i][0] < ge:
            s, e, name = segs[i]
            d = min(e, ge) - max(s, gs)
            if d > 0:
                total[name] += d
                covered += d
            i += 1
        total["host: untraced"] += (ge - gs) - covered
    ranked = sorted(((k, v) for k, v in total.items() if v > 0),
                    key=lambda kv: -kv[1])[:n]
    return [[name, ns / 1e9] for name, ns in ranked]


def idle_share(ctx) -> float:
    """Percent of the traced window in which no op ran, averaged over the
    cell's chips."""
    lo, hi = ctx.window_ns
    idle = [1.0 - sum(e - s for s, e in ctx.busy(p)) / (hi - lo)
            for p in ctx.planes]
    return 100.0 * sum(idle) / len(idle)


def host_ms_per_launch(ctx):
    """Mean over ``bench.launch`` spans of the part in which no op ran on
    any of the cell's chips, in ms; None without a launch."""
    lo, hi = ctx.window_ns
    launches = [s for s in spans(ctx.events, "bench.launch")
                if s.start_ns >= lo and s.end_ns <= hi]
    if not launches:
        return None
    merged = union(iv for p in ctx.planes for iv in ctx.busy(p))
    host = [s.dur_ns - overlap(s.start_ns, s.end_ns, merged)
            for s in launches]
    return sum(host) / len(host) / 1e6
