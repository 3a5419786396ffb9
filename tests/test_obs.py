"""Cross-layer observability (src/repro/obs): span tracer contracts
(spans and instants on the profiler's timeline, one line per thread,
on exactly while a profiler session records, allocation-free disabled
path), the launch path's spans under the SLO scheduler, the compile
counter, the always-on event log and its runtime routing (watchdog
timeouts, plan-cache evictions, arbiter rebalances), plan decision
audits with concrete rejection reasons, the metrics registry and its
Prometheus exposition, telemetry shard columns, and the calibration
drift monitor's flag/recalibrate loop."""
import collections
import glob
import gzip
import json
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.core import plan as plan_mod
from repro.core.calibrate_cost import CalibrationTable
from repro.core.ip import SiteSpec
from repro.core.plan import (NetworkPlan, clear_plan_cache, plan_network,
                             replan)
from repro.core.resources import Footprint, ResourceBudget, hbm_cycles
from repro.models.blocks import cnn_block_site_specs
from repro.models.frontends import init_cnn_frontend
from repro.obs import (COMPILES, EVENTS, NOOP_SPAN, TRACER, DriftMonitor,
                       MetricsRegistry, PlanAudit, log_event,
                       mis_scaled_table, percentile, system_metrics,
                       unfit_reason)
from repro.runtime import AdaptiveServer, SLOScheduler, SLOSpec
from repro.runtime.fault_tolerance import Watchdog
from repro.runtime.telemetry import TenantTelemetry


@pytest.fixture(autouse=True)
def _clean_obs():
    """Every test starts and ends with no profiler session (so the
    tracer off) and the event log empty — the singletons must not leak
    across tests."""
    assert not TRACER.enabled
    EVENTS.clear()
    yield
    assert not TRACER.enabled
    EVENTS.clear()


def _block_specs(site="obs"):
    specs, _ = cnn_block_site_specs((2, 16, 16, 4), (3, 3, 4, 16),
                                    x_dtype="float32", site=site)
    return tuple(specs)


HostEvent = collections.namedtuple(
    "HostEvent", "line name start_ns end_ns stats")


def _profiled(tmp_path, fn):
    """Run ``fn`` under a CPU profiler session writing into ``tmp_path``;
    return the host events of the trace (``line`` tells threads apart)."""
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for i, line in enumerate(plane.lines):
            for ev in line.events:
                out.append(HostEvent((plane.name, i), ev.name, ev.start_ns,
                                     ev.start_ns + ev.duration_ns,
                                     {k: v for k, v in ev.stats}))
    return out


def _named(events, name):
    return [e for e in events if e.name == name]


def _within(inner, outer):
    return (outer.line == inner.line and outer.start_ns <= inner.start_ns
            and inner.end_ns <= outer.end_ns)


# --------------------------------------------------------------------------
# Span tracer
# --------------------------------------------------------------------------
def test_tracer_disabled_path_is_noop_singleton():
    assert not TRACER.enabled
    # The disabled path hands back the one shared object — nothing to
    # allocate, nothing recorded.
    assert TRACER.span("anything", k=1) is NOOP_SPAN
    with TRACER.span("x") as s:
        assert s is NOOP_SPAN
    assert TRACER.instant("marker") is None


def test_tracer_records_spans_and_instants(tmp_path):
    def work():
        with TRACER.span("work", n=3, who="test"):
            TRACER.instant("tick", k=1.5)
    events = _profiled(tmp_path, work)
    (span,) = _named(events, "work")
    (tick,) = _named(events, "tick")
    assert span.stats == {"n": 3, "who": "test"}
    assert tick.stats == {"k": 1.5}
    assert _within(tick, span)
    assert tick.end_ns - tick.start_ns < span.end_ns - span.start_ns


def test_tracer_thread_safety(tmp_path):
    # The barrier holds all 8 threads alive at once, so each gets a
    # line of its own on the timeline.
    barrier = threading.Barrier(8)

    def worker():
        barrier.wait()
        for _ in range(50):
            with TRACER.span("w", worker=1):
                pass

    def run():
        threads = [threading.Thread(target=worker) for _ in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=30)
        assert not any(t.is_alive() for t in threads)

    spans = _named(_profiled(tmp_path, run), "w")
    assert len(spans) == 8 * 50
    assert len({s.line for s in spans}) == 8


def test_tracer_enabled_follows_the_profiler_session(tmp_path):
    assert not TRACER.enabled
    seen = []
    _profiled(tmp_path, lambda: seen.append(TRACER.enabled))
    assert seen == [True]
    assert not TRACER.enabled
    assert TRACER.span("after") is NOOP_SPAN


def test_profiler_trace_writes_spans_to_a_perfetto_timeline(tmp_path):
    # What an operator runs in place of an exporter of the tracer's own.
    with jax.profiler.trace(str(tmp_path), create_perfetto_trace=True):
        with TRACER.span("timeline.span", n=2):
            jnp.ones(8).block_until_ready()
    (path,) = glob.glob(f"{tmp_path}/**/perfetto_trace.json.gz",
                        recursive=True)
    with gzip.open(path) as f:
        doc = json.load(f)
    names = {e.get("name") for e in doc["traceEvents"]}
    assert "timeline.span" in names


def test_event_log_mirrors_into_enabled_tracer(tmp_path):
    EVENTS.log("test.quiet", value=1)          # no session: log only
    events = _profiled(tmp_path,
                       lambda: EVENTS.log("test.kind", value=7))
    (ev,) = _named(events, "test.kind")
    assert ev.stats == {"value": 7}
    assert not _named(events, "test.quiet")
    assert [e["kind"] for e in EVENTS.recent()] == ["test.quiet",
                                                    "test.kind"]


# The launch path's spans: name -> the span it nests in (None: none of
# the program's).
LAUNCH_SPANS = {
    "sched.admit": None, "arbiter.split": None, "sched.launch": None,
    "serve.execute": "sched.launch", "serve.stack": "serve.execute",
    "serve.plan": "serve.execute", "serve.dispatch": "serve.execute",
    "serve.results": "serve.execute", "sched.block": "sched.launch",
    "sched.judge": "sched.launch",
}


def test_served_launch_writes_every_span_nested(tmp_path):
    clear_plan_cache()
    srv = AdaptiveServer(ResourceBudget(vpu_ops_budget=15_000_000),
                         max_batch=4)
    sched = SLOScheduler(srv)
    sched.register("t", init_cnn_frontend(jax.random.PRNGKey(0),
                                          channels=(6, 12), d_model=16),
                   (12, 12, 6), slo=SLOSpec(deadline_s=60.0))
    rng = np.random.default_rng(0)
    frames = rng.normal(size=(8, 12, 12, 6)).astype(np.float32)
    sched.submit("t", frames[:4])
    sched.run()                                    # warm: launch 0
    sched.submit("t", frames[4:])
    events = _profiled(tmp_path, sched.run)        # launch 1
    assert sched.launches == 2
    for name, parent in LAUNCH_SPANS.items():
        got = _named(events, name)
        assert got, f"no {name} span"
        for e in got:
            assert e.stats["launch"] == 1, (name, e.stats)
            if parent is not None:
                assert any(_within(e, p) for p in _named(events, parent)), \
                    f"{name} is not inside {parent}"
    (launch,) = _named(events, "sched.launch")
    assert launch.stats["tenant"] == "t" and launch.stats["batch"] == 4
    assert launch.stats["wait_ms"] >= 0.0
    assert sum(e.stats["admitted"] for e in _named(events, "sched.admit")) \
        == 4
    (execute,) = _named(events, "serve.execute")
    (dispatch,) = _named(events, "serve.dispatch")
    assert dispatch.stats["compiled"] == "hit"    # launch 0 traced it
    (block,) = _named(events, "sched.block")
    (judge,) = _named(events, "sched.judge")
    assert execute.end_ns <= block.start_ns <= block.end_ns \
        <= judge.start_ns
    for leaf in ("sched.admit", "arbiter.split"):
        for e in _named(events, leaf):
            assert not _within(launch, e) and not _within(e, launch)


def test_compile_counter_counts_a_fresh_trace_once(tmp_path):
    def obs_counted_fn(x):
        return x * 3.0 + 1.0

    # JAX names a backend compile after the jitted computation
    name = obs_counted_fn.__name__
    compiled = f"jit({name})"
    traces0 = COMPILES.counts("jit.trace").get(name, 0)
    compiles0 = COMPILES.counts("jit.compile").get(compiled, 0)
    f = jax.jit(obs_counted_fn)
    x = jnp.ones(4)
    f(x).block_until_ready()
    assert COMPILES.counts("jit.trace").get(name, 0) == traces0 + 1
    assert COMPILES.counts("jit.compile").get(compiled, 0) == compiles0 + 1
    f(x).block_until_ready()                   # cached: no count
    assert COMPILES.counts("jit.trace")[name] == traces0 + 1
    assert COMPILES.counts("jit.compile")[compiled] == compiles0 + 1

    # while the profiler records, a retrace is an instant on the timeline
    events = _profiled(tmp_path,
                       lambda: f(jnp.ones(5)).block_until_ready())
    marks = [e for e in _named(events, "jit.trace")
             if e.stats["fun"] == name]
    assert len(marks) == 1 and marks[0].stats["seconds"] >= 0.0
    assert COMPILES.counts("jit.trace")[name] == traces0 + 2


# --------------------------------------------------------------------------
# Event log + runtime routing
# --------------------------------------------------------------------------
def test_watchdog_timeout_routes_to_event_log():
    fired = threading.Event()
    wd = Watchdog(timeout_s=0.05, on_timeout=fired.set)
    wd.start()
    assert fired.wait(timeout=5.0)
    wd.stop()
    events = EVENTS.recent(kind="watchdog.timeout")
    assert events and events[-1]["timeout_s"] == pytest.approx(0.05)


def test_plan_cache_eviction_routes_to_event_log():
    clear_plan_cache()
    specs = _block_specs()
    old_max = plan_mod._PLAN_CACHE_MAX
    plan_mod._PLAN_CACHE_MAX = 1
    try:
        plan_network(specs, ResourceBudget())
        plan_network(specs, ResourceBudget(vmem_bytes=2 * 2**20))
    finally:
        plan_mod._PLAN_CACHE_MAX = old_max
    evs = EVENTS.recent(kind="plan_cache.evict")
    assert evs and evs[-1]["capacity"] == 1


def test_arbiter_rebalance_routes_to_event_log():
    from repro.runtime import BudgetArbiter
    arb = BudgetArbiter(ResourceBudget(), rebalance_threshold=0.01,
                        demand_alpha=1.0)
    arb.register("a")
    arb.register("b")
    arb.split()                         # first grant: no rebalance
    arb.observe("a", 1000.0)
    arb.split()                         # demand skew past threshold
    assert arb.rebalances == 1
    evs = EVENTS.recent(kind="arbiter.rebalance")
    assert evs and evs[-1]["cause"] == "drift"


# --------------------------------------------------------------------------
# Plan decision audit
# --------------------------------------------------------------------------
def test_unfit_reason_names_the_failing_axis():
    fp = Footprint(vmem_bytes=700 * 1024, hbm_bytes=1024, mxu_passes=0,
                   vpu_ops=100, est_cycles=1000.0)
    reason = unfit_reason(fp, ResourceBudget(vmem_bytes=600 * 1024))
    assert "vmem" in reason and "700KiB" in reason and "600KiB" in reason
    reason = unfit_reason(
        Footprint(vmem_bytes=10, hbm_bytes=10, mxu_passes=4, vpu_ops=0,
                  est_cycles=1.0),
        ResourceBudget(mxu_available=False))
    assert "mxu_available=False" in reason


def test_plan_audit_names_concrete_rejection_reasons():
    clear_plan_cache()
    specs = _block_specs()
    ample = plan_network(specs, ResourceBudget())
    assert ample.audit is not None
    # Squeeze the VPU path: any site whose choice moved must carry a
    # concrete rejection for the member it abandoned.
    tight = plan_network(specs,
                         ResourceBudget(vpu_ops_budget=100_000))
    moved = [s for s, a in zip(tight.sites, ample.sites)
             if s.ip.name != a.ip.name
             or s.precision_bits != a.precision_bits]
    assert moved, "budget squeeze did not move any site"
    for site in moved:
        audit = tight.audit.site(site.spec.name)
        reasons = audit.rejection_reasons()
        assert reasons, f"no rejection recorded for {site.spec.name}"
        assert any(ch.isdigit() for r in reasons for ch in r), \
            "rejection reasons must carry concrete numbers"
    assert tight.explain()


def test_plan_audit_roundtrips_through_json():
    clear_plan_cache()
    specs = _block_specs()
    plan = plan_network(specs, ResourceBudget(vpu_ops_budget=100_000))
    back = NetworkPlan.from_json(plan.to_json())
    assert back.audit is not None
    assert back.audit.to_dict() == plan.audit.to_dict()
    assert back.explain() == plan.explain()


def test_cached_plan_keeps_its_audit():
    clear_plan_cache()
    specs = _block_specs()
    cold = plan_network(specs, ResourceBudget())
    warm = plan_network(specs, ResourceBudget())
    assert warm is cold and warm.audit is not None


def test_replan_fast_path_records_audit_event():
    clear_plan_cache()
    specs = _block_specs()
    plan_network(specs, ResourceBudget())        # warms the share cache
    plan = replan(specs, ResourceBudget().scaled(0.7))
    assert plan.audit is not None
    assert any("replan fast path" in e for e in plan.audit.events)


def test_explain_handles_missing_audit():
    plan = NetworkPlan(budget=ResourceBudget(), sites=())
    assert "no audit" in plan.explain()


# --------------------------------------------------------------------------
# Metrics registry
# --------------------------------------------------------------------------
def test_registry_counter_gauge_histogram_and_render():
    reg = MetricsRegistry(namespace="t")
    reg.counter("reqs", "served requests", tenant="a").inc(3)
    reg.gauge("depth").set(2.5)
    h = reg.histogram("lat", "latency")
    h.observe_many([1.0, 2.0, 3.0, 4.0])
    snap = reg.snapshot()
    assert snap["reqs"][0]["value"] == 3
    assert snap["lat"][0]["count"] == 4
    text = reg.render()
    assert "# TYPE t_reqs counter" in text
    assert 't_reqs{tenant="a"} 3' in text
    assert "# TYPE t_lat summary" in text
    assert "t_lat_count 4" in text
    assert 't_lat{quantile="0.5"} 2.5' in text


def test_registry_is_idempotent_but_kind_conflicts_raise():
    reg = MetricsRegistry()
    c = reg.counter("x")
    assert reg.counter("x") is c
    with pytest.raises(ValueError, match="already registered"):
        reg.gauge("x")


def test_registry_labels_may_shadow_registration_args():
    # system_metrics renders event counts labelled kind=...; label names
    # must never collide with _get's own parameters
    reg = MetricsRegistry(namespace="t")
    reg.counter("events", "event-log entries",
                kind="watchdog.timeout", name="n", help_="h").inc(2)
    text = reg.render()
    assert 'kind="watchdog.timeout"' in text and 'name="n"' in text


def test_system_metrics_counts_logged_events_by_kind():
    log_event("watchdog.timeout", timeout_s=0.1)
    log_event("watchdog.timeout", timeout_s=0.2)
    text = system_metrics().render()
    assert 'repro_events_total{kind="watchdog.timeout"} 2' in text


def test_system_metrics_exposes_jit_traces_and_compiles():
    def obs_exposed_fn(x):
        return x - 2.0

    jax.jit(obs_exposed_fn)(jnp.ones(3)).block_until_ready()
    text = system_metrics().render()
    assert "# TYPE repro_jit_traces_total counter" in text
    assert 'repro_jit_traces_total{fun="obs_exposed_fn"} 1' in text
    assert 'repro_jit_compiles_total{fun="jit(obs_exposed_fn)"} 1' in text
    assert "tracer_" not in text


def test_counter_rejects_negative_increment():
    reg = MetricsRegistry()
    with pytest.raises(ValueError, match="only go up"):
        reg.counter("c").inc(-1)


def test_system_metrics_includes_tenant_shard_columns():
    clear_plan_cache()
    srv = AdaptiveServer(ResourceBudget(), max_batch=2)
    srv.register("t", init_cnn_frontend(jax.random.PRNGKey(0),
                                        channels=(6, 12), d_model=16),
                 (12, 12, 6))
    rng = np.random.default_rng(0)
    srv.submit("t", rng.normal(size=(12, 12, 6)).astype(np.float32))
    srv.drain()
    text = srv.metrics().render()
    assert 'repro_tenant_shard_degree{tenant="t"} 1' in text
    assert 'repro_tenant_comm_cycles_share{tenant="t"} 0' in text
    assert 'repro_tenant_requests_total{tenant="t"} 1' in text
    assert srv.queue_stats()["popped_requests"] == 1


# --------------------------------------------------------------------------
# Telemetry shard columns + shared percentile
# --------------------------------------------------------------------------
def _planned_site_stub(deg, comm, est):
    class _S:
        precision_bits = 32
        shard_degree = deg
        footprint = Footprint(vmem_bytes=1, hbm_bytes=0, mxu_passes=0,
                              vpu_ops=0, est_cycles=est, comm_cycles=comm)
    return _S()


def test_telemetry_snapshot_gains_shard_columns():
    tel = TenantTelemetry(name="t", max_batch=4)

    class _Plan:
        sites = (_planned_site_stub(4, 250.0, 1000.0),
                 _planned_site_stub(1, 0.0, 1000.0))

    tel.record_batch(2, [10.0, 12.0], _Plan(), cache_hits=1,
                     cache_misses=0)
    snap = tel.snapshot()
    assert snap["shard_degree"] == 4
    assert snap["shard_degree_mix"] == {1: 1, 4: 1}
    assert snap["comm_cycles_share"] == pytest.approx(250.0 / 2000.0)


def test_latency_percentile_delegates_to_shared_estimator():
    tel = TenantTelemetry(name="t", max_batch=4)
    tel.latencies.extend([5.0, 1.0, 3.0, 2.0, 4.0])
    for q in (0, 25, 50, 90, 100):
        assert tel.latency_percentile(q) == percentile(
            [1.0, 2.0, 3.0, 4.0, 5.0], q)


# --------------------------------------------------------------------------
# Calibration drift monitor
# --------------------------------------------------------------------------
def _fp(compute=1000.0, hbm=4096):
    return Footprint(vmem_bytes=1024, hbm_bytes=hbm, mxu_passes=0,
                     vpu_ops=100, est_cycles=compute + hbm_cycles(hbm))


def _fitted_table(a=0.002, b=1e-6, c=5.0):
    """A table fit on points lying exactly on us = a*compute + b*hbm + c."""
    table = CalibrationTable()
    for comp, hbm in ((1000.0, 4096), (2000.0, 8192), (4000.0, 2048),
                      (8000.0, 16384)):
        table.record("m", _fp(comp, int(hbm)), a * comp + b * hbm + c)
    return table.fit(min_samples=3)


def test_drift_monitor_quiet_on_honest_table():
    table = _fitted_table()
    mon = DriftMonitor(table, threshold=0.5, min_observations=3)
    for comp in (1500.0, 2500.0, 3500.0, 4500.0):
        fp = _fp(comp)
        truth = 0.002 * comp + 1e-6 * fp.hbm_bytes + 5.0
        assert mon.observe("m", fp, truth) is None
    assert not mon.drifted
    assert mon.mean_rel_error < 0.05


def test_drift_monitor_flags_mis_scaled_table_once():
    table = _fitted_table()
    bad = mis_scaled_table(table, 8.0)
    hits = []
    mon = DriftMonitor(bad, threshold=0.5, min_observations=3,
                       on_drift=hits.append)
    report = None
    for comp in (1500.0, 2500.0, 3500.0, 4500.0):
        fp = _fp(comp)
        truth = 0.002 * comp + 1e-6 * fp.hbm_bytes + 5.0
        report = mon.observe("m", fp, truth) or report
    assert mon.drifted and report is not None
    assert report.mean_rel_error > 0.5
    assert len(hits) == 1               # one flag per excursion
    assert len(mon.reports) == 1
    assert EVENTS.recent(kind="calibration.drift")


def test_drift_monitor_recalibrate_rearms_and_quiets():
    table = _fitted_table()
    bad = mis_scaled_table(table, 8.0)
    mon = DriftMonitor(bad, threshold=0.5, min_observations=3)
    obs = []
    for comp in (1500.0, 2500.0, 3500.0, 4500.0):
        fp = _fp(comp)
        truth = 0.002 * comp + 1e-6 * fp.hbm_bytes + 5.0
        obs.append((fp, truth))
        mon.observe("m", fp, truth)
    assert mon.drifted
    before = bad.fingerprint()
    after = mon.recalibrate()
    assert after != before              # refit moved the table identity
    assert not mon.drifted
    for fp, truth in obs:               # the refit table predicts truth
        assert mon.observe("m", fp, truth) is None
    assert not mon.drifted
    assert EVENTS.recent(kind="calibration.refit")


def test_drift_monitor_no_verdict_without_fit():
    mon = DriftMonitor(CalibrationTable(), threshold=0.5,
                       min_observations=1)
    assert mon.observe("m", _fp(), 10.0) is None
    assert mon.predictions == 0 and mon.observations == 1
