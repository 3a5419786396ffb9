"""Observability demo — watch every layer of the stack decide.

Four views onto one small CNN serving stack, narrated end to end:

1. AUDIT   — plan the network under an ample and then a constrained
   budget; ``NetworkPlan.explain()`` names the concrete clause that
   rejected every candidate the selector passed over (vmem overflow,
   VPU starvation, precision-ladder descent) plus plan-level events
   (fusion decisions, partition repairs, shard refusals).
2. TRACE   — run a multi-tenant serving cycle under the JAX profiler,
   which writes the program's spans beside the device ops into one
   Perfetto timeline (open it at ui.perfetto.dev): plan/replan spans,
   frame stacks, kernel dispatch, arbiter splits, JAX compiles.  With
   no profiler session the tracer costs the hot loop nothing.
3. METRICS — render the server's state as Prometheus-style text:
   per-tenant request counts, latency quantiles, shard degree,
   comm-cycles share, plan-cache size.
4. DRIFT   — fit a calibration table, then compare an honest and an
   8x mis-scaled copy against fresh measurements: the drift monitor
   stays quiet on the first, trips on the second, and
   ``recalibrate()`` refits it quiet again.

See docs/adaptive_ips.md, "Observability contract", and
benchmarks/run.py::table_obs for the asserted version of this loop.

    PYTHONPATH=src python examples/observability_demo.py
"""
import collections
import shutil
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT / "src"))

import numpy as np  # noqa: E402

from repro.core.calibrate_cost import (collect_plan_samples,  # noqa: E402
                                       measure_planned_site, member_key)
from repro.core.plan import clear_plan_cache, plan_network  # noqa: E402
from repro.core.resources import ResourceBudget  # noqa: E402
from repro.models.blocks import cnn_block_site_specs  # noqa: E402
from repro.obs import EVENTS, DriftMonitor, mis_scaled_table  # noqa: E402

LAYERS = [(8, 16), (16, 32), (32, 32)]


def network_specs():
    specs, shape = [], (2, 32, 32, LAYERS[0][0])
    for li, (cin, cout) in enumerate(LAYERS):
        layer, out = cnn_block_site_specs(
            shape, (3, 3, cin, cout), x_dtype="float32", pool_mode="max",
            activation="relu", site=f"layer{li}", ladder=(16, 8))
        specs += layer
        shape = out.shape
    return tuple(specs)


def serving_cycle():
    """One small two-tenant serving trace; returns the server."""
    import jax

    from repro.models.frontends import init_cnn_frontend
    from repro.runtime import AdaptiveServer

    clear_plan_cache()
    device = ResourceBudget(vpu_ops_budget=60_000_000,
                            vmem_bytes=12 * 1024 * 1024)
    heavy = init_cnn_frontend(jax.random.PRNGKey(0), channels=(8, 16),
                              d_model=32)
    light = init_cnn_frontend(jax.random.PRNGKey(1), channels=(6, 12),
                              d_model=16)
    srv = AdaptiveServer(device, policy="demand", max_batch=4)
    srv.register("vision-heavy", heavy, (32, 32, 8))
    srv.register("edge-light", light, (24, 24, 6), activation="tanh",
                 ladder=(16, 8))
    rng = np.random.default_rng(0)
    # demand flips between waves so the arbiter actually re-balances
    # (and logs an ``arbiter.rebalance`` event) mid-trace
    for n_heavy, n_light in ((4, 1), (1, 4)):
        for _ in range(n_heavy):
            srv.submit("vision-heavy",
                       rng.normal(size=(32, 32, 8)).astype(np.float32))
        for _ in range(n_light):
            srv.submit("edge-light",
                       rng.normal(size=(24, 24, 6)).astype(np.float32))
        srv.step()
    return srv


def main():
    specs = network_specs()

    print("== 1. AUDIT: why did the plan choose what it chose? ==")
    clear_plan_cache()
    ample = plan_network(specs, ResourceBudget())
    tight = plan_network(specs, ResourceBudget(vpu_ops_budget=2_000_000))
    moved = [s.spec.name for s in tight.sites
             if (s.ip.name, s.precision_bits) != next(
                 ((a.ip.name, a.precision_bits) for a in ample.sites
                  if a.spec.name == s.spec.name), None)]
    print(f"  ample plan: {len(ample.sites)} sites; the VPU-starved "
          f"budget moved {len(moved)} site choices")
    print("  --- tight.explain() ---")
    print("\n".join("  " + line
                    for line in tight.explain().splitlines()))

    print("\n== 2. TRACE: a serving cycle under the profiler ==")
    import jax
    serving_cycle()                      # warm compile caches untraced
    EVENTS.clear()
    out = ROOT / "experiments" / "obs" / "demo_trace"
    shutil.rmtree(out, ignore_errors=True)
    with jax.profiler.trace(str(out), create_perfetto_trace=True):
        srv = serving_cycle()
    metrics_text = srv.metrics().render()
    (xplane,) = out.glob("**/*.xplane.pb")
    (timeline,) = out.glob("**/perfetto_trace.json.gz")
    names = collections.Counter(
        ev.name for plane in jax.profiler.ProfileData.from_file(
            str(xplane)).planes if plane.name.startswith("/host:")
        for line in plane.lines for ev in line.events)
    for name in ("arbiter.split", "serve.execute", "serve.stack",
                 "serve.plan", "serve.dispatch", "serve.results",
                 "replan", "plan_network"):
        print(f"  {name:15s} {names[name]:4d} spans")
    print(f"  -> {timeline} (load at ui.perfetto.dev)")
    print("  event log (always on, even with the tracer off):")
    for ev in EVENTS.recent(4):
        print(f"    {ev['kind']}: "
              + ", ".join(f"{k}={v}" for k, v in sorted(ev.items())
                          if k not in ("kind", "t")))

    print("\n== 3. METRICS: Prometheus-style exposition ==")
    wanted = ("repro_tenant_requests", "repro_tenant_shard_degree",
              "repro_plan_cache_size", "quantile=\"0.5\"")
    for line in metrics_text.splitlines():
        if any(w in line for w in wanted):
            print(f"  {line}")

    print("\n== 4. DRIFT: honest table quiet, mis-scaled table loud ==")
    clear_plan_cache()
    plan = plan_network(specs, ResourceBudget())
    for site in plan.sites:          # discard a warm pass per site so the
        measure_planned_site(site, repeat=1)  # fit sees the warm regime
    table = collect_plan_samples([plan], repeat=2).fit()
    honest = DriftMonitor(table, threshold=2.0, min_observations=3)
    lying = DriftMonitor(mis_scaled_table(table, 8.0), threshold=2.0,
                         min_observations=3)
    for site in plan.sites:
        member = member_key(site.ip.name, site.precision_bits,
                            site.spec.native_bits)
        us = measure_planned_site(site, repeat=2)
        honest.observe(member, site.footprint, us)
        lying.observe(member, site.footprint, us)
    print(f"  honest table:    drifted={honest.drifted} "
          f"(mean rel err {honest.mean_rel_error:.2f})")
    print(f"  8x mis-scaled:   drifted={lying.drifted} "
          f"(mean rel err {lying.mean_rel_error:.2f})")
    assert not honest.drifted and lying.drifted
    lying.recalibrate()
    for site in plan.sites:
        member = member_key(site.ip.name, site.precision_bits,
                            site.spec.native_bits)
        lying.observe(member, site.footprint,
                      measure_planned_site(site, repeat=2))
    print(f"  after recalibrate(): drifted={lying.drifted} "
          f"(mean rel err {lying.mean_rel_error:.2f})")
    assert not lying.drifted
    print("  -> the stale cost model was caught from serving-shaped "
          "samples\n     and refit without replanning by hand")


if __name__ == "__main__":
    main()
