"""Cross-layer observability: span tracing, plan decision audit,
metrics exposition, calibration-drift detection.

The planner picks members, the arbiter moves grants, the mesh pass
prices collectives, and the calibration table claims to predict
wall-clock — this package is how an operator *sees* any of it:

* ``obs.trace``   — span tracer on the JAX profiler's timeline (on
  while a profiler session records), the always-on compile counter, and
  the always-on bounded event log for operator events (watchdog
  firings, plan-cache evictions, drift trips).
* ``obs.audit``   — the plan decision audit: per-site candidate sets
  with concrete rejection reasons, surfaced via
  ``NetworkPlan.explain()``.
* ``obs.metrics`` — one registry unifying the scattered stats (plan
  cache, arbiter, tenant telemetry, queue depth) behind a snapshot and
  Prometheus-style text exposition; owns the shared percentile
  estimator ``telemetry.latency_percentile`` delegates to.
* ``obs.drift``   — online comparison of calibrated predictions vs
  measured wall-clock, flagging when the table has drifted, with a
  recalibration hook back into ``core/calibrate_cost.py``.

Import discipline: these modules import nothing from ``repro.core`` or
``repro.runtime`` at module level (collector functions import lazily),
so the planner and the runtime can import obs without cycles.  See
docs/adaptive_ips.md, "Observability contract".
"""
from repro.obs.audit import (CandidateRecord, PlanAudit, SiteAudit,
                             SiteAuditRecorder, unfit_reason)
from repro.obs.drift import DriftMonitor, DriftReport, mis_scaled_table
from repro.obs.metrics import (Counter, Gauge, Histogram, MetricsRegistry,
                               percentile, system_metrics)
from repro.obs.trace import (COMPILES, EVENTS, NOOP_SPAN, TRACER,
                             CompileCounter, EventLog, SpanTracer, log_event)

__all__ = [
    "CandidateRecord", "PlanAudit", "SiteAudit", "SiteAuditRecorder",
    "unfit_reason",
    "DriftMonitor", "DriftReport", "mis_scaled_table",
    "Counter", "Gauge", "Histogram", "MetricsRegistry", "percentile",
    "system_metrics",
    "COMPILES", "EVENTS", "NOOP_SPAN", "TRACER", "CompileCounter", "EventLog",
    "SpanTracer", "log_event",
]
