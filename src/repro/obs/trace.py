"""Span tracer, compile counter and event log — the timing half of the
observability layer.

Three instruments:

* ``SpanTracer`` (singleton ``TRACER``) writes *spans* — named host
  intervals — and instant markers straight into the JAX profiler's
  trace (``jax.profiler.TraceAnnotation``).  The profiler is the only
  backend and its clock is the only clock, so the program's spans line
  up with the device planes of the same ``.xplane.pb``.  Tracing is on
  exactly while a profiler session records (``jax.profiler.trace`` or
  ``start_trace``/``stop_trace``); there is no switch of its own.  Span
  names are fixed strings; whatever varies goes in the stats (keyword
  arguments).  The disabled path allocates nothing: hot call sites guard
  with ``if TRACER.enabled`` and take the shared ``NOOP_SPAN`` singleton
  on the else branch — no stats dict, no annotation object.  The idiom::

      with (TRACER.span("serve.execute", tenant=name, batch=n)
            if TRACER.enabled else NOOP_SPAN):
          ...

  costs one ``TraceAnnotation.is_enabled()`` call and one branch when no
  profiler records.  For a timeline, run the code under
  ``jax.profiler.trace(dir, create_perfetto_trace=True)``: the file holds
  these spans beside the device ops.

* ``CompileCounter`` (singleton ``COMPILES``) listens, through
  ``jax.monitoring``, for JAX's tracing and backend-compile events.  It
  always counts them by (event, function name), and while the profiler
  records it marks each one on the timeline as a ``jit.trace`` or
  ``jit.compile`` instant (stats ``fun``, ``seconds``), so a retrace
  shows inside the span it fell in.

* ``EventLog`` (singleton ``EVENTS``) is **always on**: a small bounded
  ring of operator-relevant events (watchdog timeouts, plan-cache
  evictions, arbiter rebalances, calibration drift trips) that would
  otherwise be invisible.  Events mirror onto the timeline as instants
  while the profiler records.
"""
from __future__ import annotations

import collections
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

import jax
from jax.profiler import TraceAnnotation

EVENT_LOG_MAX = 1024

# JAX's duration events for one tracing to a jaxpr and one backend
# compile, and the instant each becomes on the timeline.
JIT_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "jit.trace",
    "/jax/core/compile/backend_compile_duration": "jit.compile",
}


class _NoopSpan:
    """The shared disabled-path context manager: enter/exit do nothing,
    and the single module-level instance (``NOOP_SPAN``) means the
    disabled hot path allocates nothing."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


NOOP_SPAN = _NoopSpan()


class SpanTracer:
    """Spans on the profiler's timeline; see module docstring.  Use the
    ``TRACER`` singleton."""

    __slots__ = ()

    @property
    def enabled(self) -> bool:
        """True while a profiler session records."""
        return TraceAnnotation.is_enabled()

    def span(self, name: str, /, **stats):
        """A context manager for one span named ``name`` with ``stats``.
        Hot call sites guard with ``if TRACER.enabled`` and take
        ``NOOP_SPAN`` otherwise; while no profiler records this returns
        ``NOOP_SPAN`` too, so un-guarded cold sites stay correct."""
        if not TraceAnnotation.is_enabled():
            return NOOP_SPAN
        return TraceAnnotation(name, **stats)

    def instant(self, name: str, /, **stats) -> None:
        """A zero-length marker."""
        if TraceAnnotation.is_enabled():
            with TraceAnnotation(name, **stats):
                pass


TRACER = SpanTracer()


class CompileCounter:
    """Counts JAX's tracings and backend compiles; see module
    docstring.  Its ``__call__`` is a ``jax.monitoring`` duration
    listener."""

    def __init__(self):
        self._counts: Dict[Tuple[str, str], int] = collections.Counter()
        self._lock = threading.Lock()

    def __call__(self, event: str, duration_secs: float, **kwargs) -> None:
        name = JIT_EVENTS.get(event)
        if name is None:
            return
        fun = str(kwargs.get("fun_name", "?"))
        with self._lock:
            self._counts[(name, fun)] += 1
        TRACER.instant(name, fun=fun, seconds=duration_secs)

    def counts(self, name: str) -> Dict[str, int]:
        """Function name -> count of one instant kind (``jit.trace`` or
        ``jit.compile``) since the process started."""
        with self._lock:
            return {fun: n for (kind, fun), n in self._counts.items()
                    if kind == name}


COMPILES = CompileCounter()
jax.monitoring.register_event_duration_secs_listener(COMPILES)


class EventLog:
    """Always-on bounded ring of operator events; see module docstring."""

    def __init__(self, max_events: int = EVENT_LOG_MAX):
        self.max_events = max_events
        self.total = 0
        self._events: List[Dict[str, Any]] = []
        self._lock = threading.Lock()

    def log(self, kind: str, **fields) -> None:
        """Record one event.  ``kind`` is a dotted taxonomy name
        (``"watchdog.timeout"``, ``"plan_cache.evict"``); fields are
        free-form JSON-able payload.  Mirrors onto the profiler's
        timeline as an instant while a profiler session records."""
        event = {"kind": kind, "t": time.time(), **fields}
        with self._lock:
            self.total += 1
            self._events.append(event)
            if len(self._events) > self.max_events:
                del self._events[:len(self._events) - self.max_events]
        TRACER.instant(kind, **fields)

    def recent(self, n: int = 50, kind: Optional[str] = None) -> List[dict]:
        with self._lock:
            events = list(self._events)
        if kind is not None:
            events = [e for e in events if e["kind"] == kind]
        return events[-n:]

    def counts(self) -> Dict[str, int]:
        """Events per kind currently in the ring (bounded window)."""
        out: Dict[str, int] = {}
        with self._lock:
            for e in self._events:
                out[e["kind"]] = out.get(e["kind"], 0) + 1
        return out

    def clear(self) -> None:
        with self._lock:
            self._events.clear()
            self.total = 0


EVENTS = EventLog()


def log_event(kind: str, **fields) -> None:
    """Module-level shorthand for ``EVENTS.log`` — what the planner,
    watchdog, arbiter and drift monitor call."""
    EVENTS.log(kind, **fields)
