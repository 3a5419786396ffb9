"""Forced-multi-device child for ``benchmarks/run.py::table_chaos``.

Launched by the parent bench with
``XLA_FLAGS=--xla_force_host_platform_device_count=2`` (JAX fixes its
device count at import, so the mesh arms cannot run in the parent).
Three arms over the same deterministic Poisson-spaced traffic, ONE json
object to stdout for the parent to assert on:

  transparency — the same serving trace twice: injector disarmed vs
      armed with a never-firing schedule.  Outputs, completion times
      and modeled latency percentiles must be bit-identical (the
      off-path contract of ``runtime/faults.py``).
  chaos        — the guarded deployment (SLO scheduler, output
      screening with ``retry_f32``, spare plans pre-warmed) served
      through one fault *phase per kind* — a NaN-poisoned batch, a
      corrupted collective, a kernel-launch exception, a latency
      spike, then a device loss, then a degraded soak.  Must keep
      availability >= the target, re-plan ZERO graphs cold while
      degrading 2 -> 1 devices, keep every plan at f32 (the degree
      ladder descends BEFORE the precision ladder), and bound the
      modeled p95 inflation against the fault-free run of the same
      traffic.
  baseline     — the same phases against an unguarded synchronous
      server: the NaN and collective batches are served poisoned, the
      kernel exception loses its batch, and after the device loss
      EVERY remaining batch dies on the corpse — the availability
      collapse the survival machinery exists to prevent.

Usage: python benchmarks/_chaos_child.py [soak_waves]
"""
from __future__ import annotations

import json
import sys

import jax
import numpy as np

from repro.core.plan import STATS, clear_plan_cache, replan
from repro.core.resources import MeshSpec, ResourceBudget
from repro.models.frontends import init_cnn_frontend
from repro.runtime import (AdaptiveServer, FaultSpec, GuardPolicy, INJECTOR,
                           InjectedFault, SLOScheduler, SLOSpec)

SOAK_WAVES = int(sys.argv[1]) if len(sys.argv) > 1 else 3
DEVICE = ResourceBudget(vpu_ops_budget=15_000_000)
MESH = MeshSpec(devices=2)
MAX_BATCH = 4
WAVE = 8                   # requests per phase
DEADLINE_S = 60.0          # generous: outcomes hinge on faults, not SLOs

# One phase per fault kind, each armed for its own wave of traffic
# (``step=0``: the first poll of the kind's seam in that phase fires).
# The device loss comes last so every earlier seam exercises the
# 2-device sharded path; the soak waves after it serve degraded.
PHASES = [
    ("warmup", None),
    ("nan_output", [FaultSpec("nan_output", step=0)]),
    ("collective_corrupt", [FaultSpec("collective_corrupt", step=0)]),
    ("kernel_exception", [FaultSpec("kernel_exception", step=0)]),
    ("latency_spike", [FaultSpec("latency_spike", step=0, param=4.0)]),
    ("device_loss", [FaultSpec("device_loss", step=0, param=1)]),
] + [(f"soak{i}", None) for i in range(SOAK_WAVES)]

NEVER = [FaultSpec("nan_output", step=10**9)]


def _params():
    return init_cnn_frontend(jax.random.PRNGKey(0), channels=(6, 12),
                             d_model=16)


def _traffic():
    """Deterministic Poisson-spaced single-tenant arrivals, one wave per
    phase: seeded exponential inter-arrival gaps on the est-cycles
    clock, identical across the three arms.  The gap scale is far below
    one batch's service cycles, so the continuous batcher fills batches
    to ``MAX_BATCH`` — full batches tile across the 2-device mesh, which
    is what keeps the sharded (collective) path on the serving floor."""
    rng = np.random.default_rng(0)
    n = WAVE * len(PHASES)
    xs = [rng.normal(size=(12, 12, 6)).astype(np.float32) for _ in range(n)]
    ats = np.cumsum(rng.exponential(scale=1.0, size=n))
    waves = [(xs[i * WAVE:(i + 1) * WAVE], ats[i * WAVE:(i + 1) * WAVE])
             for i in range(len(PHASES))]
    return waves


def _guarded_deployment():
    clear_plan_cache()
    srv = AdaptiveServer(DEVICE, mesh=MESH, max_batch=MAX_BATCH)
    sched = SLOScheduler(srv)
    sched.register("a", _params(), (12, 12, 6),
                   slo=SLOSpec(deadline_s=DEADLINE_S))
    srv.set_guard("a", GuardPolicy(on_nonfinite="retry_f32", max_retries=2,
                                   backoff_base_s=0.001))
    return srv, sched


def _finite(c):
    return c.ok and bool(np.isfinite(np.asarray(c.result)).all())


def _run_guarded(waves, schedule_of):
    """Serve every phase wave through a fresh guarded deployment,
    arming ``schedule_of(phase_name)`` (or nothing) around each."""
    srv, sched = _guarded_deployment()
    comps, fired = [], []
    for (name, _), (xs, ats) in zip(PHASES, waves):
        schedule = schedule_of(name)
        if schedule:
            INJECTOR.arm(schedule)
        try:
            for x, at in zip(xs, ats):
                sched.submit("a", x, at=float(at))
            comps.extend(sched.run())
            fired.extend(f[0] for f in INJECTOR.fired)
        finally:
            INJECTOR.disarm()
    return srv, sorted(comps, key=lambda c: c.rid), fired


def main() -> None:
    waves = _traffic()
    n = WAVE * len(PHASES)
    out = {"devices": len(jax.devices()), "requests": n}

    # -- transparency: disarmed vs armed-but-never-firing ----------------
    srv_off, base, _ = _run_guarded(waves, lambda name: None)
    tel_off = srv_off.telemetry()["a"]
    srv_on, armed, _ = _run_guarded(waves, lambda name: NEVER)
    tel_on = srv_on.telemetry()["a"]
    out["transparent"] = bool(
        len(base) == len(armed) == n
        and all(a.ok and a.finished == b.finished
                and bool((np.asarray(a.result)
                          == np.asarray(b.result)).all())
                for a, b in zip(base, armed))
        and tel_off["p95_cycles"] == tel_on["p95_cycles"])
    p95_healthy = tel_off["p95_cycles"]

    # -- chaos: guarded + pre-warmed spares, one fault phase per kind ----
    # (pre-warm + cold-plan accounting need hooks around the warmup
    # phase, so the loop is inlined rather than reusing _run_guarded)
    srv, sched = _guarded_deployment()
    comps, fired = [], []
    misses0 = spares = None
    for (name, schedule), (xs, ats) in zip(PHASES, waves):
        if schedule:
            INJECTOR.arm(schedule)
        try:
            for x, at in zip(xs, ats):
                sched.submit("a", x, at=float(at))
            comps.extend(sched.run())
            fired.extend(f[0] for f in INJECTOR.fired)
        finally:
            INJECTOR.disarm()
        if name == "warmup":
            # the live-deployment warm ritual: settle grants on clean
            # traffic, warm every healthy batch shape the settled grant
            # serves under, then pre-plan the post-loss spares — after
            # this point NOTHING may plan cold
            t = srv.tenants["a"]
            for b in range(1, MAX_BATCH + 1):
                specs = srv._specs(t.net, t.params, (b,) + t.input_shape,
                                   "float32", t.ladder)
                replan(specs, srv.arbiter.budget_for("a"), fuse=srv.fuse,
                       mesh=srv.arbiter.mesh_for("a"))
            spares = srv.prewarm_spares(losses=1)
            misses0 = STATS.plan_misses
    tel = srv.telemetry()["a"]
    ok = sum(1 for c in comps if _finite(c))
    out["chaos"] = {
        "submitted": n,
        "served_ok": ok,
        "availability": ok / n,
        "cold_plans": STATS.plan_misses - misses0,
        "spares_prewarmed": spares,
        "faults_fired": sorted(fired),
        "devices_after": srv.mesh.devices,
        "degradations": tel["degradations"],
        "guard_retries": tel["guard_retries"],
        "shard_degree_mix": {str(k): v
                             for k, v in tel["shard_degree_mix"].items()},
        "precision_mix": {str(k): v
                          for k, v in tel["precision_mix"].items()},
        "p95_cycles_healthy": p95_healthy,
        "p95_cycles_chaos": tel["p95_cycles"],
        "deadline_miss_rate": tel["deadline_miss_rate"],
    }

    # -- baseline: the same phases, no guards, no degradation ------------
    clear_plan_cache()
    srv = AdaptiveServer(DEVICE, mesh=MESH, max_batch=MAX_BATCH)
    srv.register("a", _params(), (12, 12, 6))
    served, lost_batches = [], 0
    corpse_persists = False
    try:
        for (name, schedule), (xs, ats) in zip(PHASES, waves):
            if schedule and not corpse_persists:
                INJECTOR.arm(schedule)
                # a lost device stays lost: with nobody degrading the
                # mesh, the corpse outlives its phase and every later
                # batch's device slice still overlaps it
                corpse_persists = name == "device_loss"
            for x, at in zip(xs, ats):
                srv.submit("a", x, at=float(at))
            while srv.pending():
                try:
                    served.extend(srv.step())
                except InjectedFault:
                    # the whole batch died; its requests are simply gone
                    lost_batches += 1
            if not corpse_persists:
                INJECTOR.disarm()
    finally:
        INJECTOR.disarm()
    ok = sum(1 for c in served if _finite(c))
    out["baseline"] = {
        "submitted": n,
        "served_ok": ok,
        "availability": ok / n,
        "lost_batches": lost_batches,
    }
    print(json.dumps(out))


if __name__ == "__main__":
    main()
