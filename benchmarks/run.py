"""Benchmark harness — one function per paper table + kernel/system benches.

Paper tables (the reproduction targets):
  table1_ip_characteristics  — Table I: capability matrix of the IP library
  table2_resource_utilization — Table II: measured per-IP resource usage
      (FPGA LUT/Reg/CLB/DSP/WNS/Power -> TPU vpu-ops/vmem/mxu-passes/
       est-cycles/us-per-call, from footprints + interpret-mode timing)
  table3_comparison          — Table III: adaptive selection vs fixed-IP
      baselines across resource budgets (the paper's adaptability claim,
      made quantitative)
  table_precision            — the precision ladder: f32-only vs
      ladder-planned networks across the budget ladder (planned cycles,
      measured wall time, and per-site quantization error)
  table_serving              — the serving runtime: static even budget
      split vs demand-arbitrated split across a load ladder (overall
      p95 latency in est-cycles, squeezed-tenant precision mix +
      measured quant error)
  table_calibration          — the measurement-calibrated cost model:
      warmup per-site samples -> affine fits -> the calibrated planner's
      fused-vs-unfused choice must match measured wall-clock on every
      fusion-ladder budget (asserted)
  table_mesh                 — mesh-sharded planning: the 2-device
      planned split must beat the best 1-device plan (modeled AND
      measured), and the planner must refuse to shard when collective
      cost outweighs the split (refusal measured via the forced-shard
      counterfactual); runs under a forced 2-device host mesh
  table_obs                  — cross-layer observability: plan audits
      must name concrete rejection reasons, a serving cycle under the
      profiler must put plan/dispatch/arbiter spans on its timeline
      within a bounded overhead of the untraced run, and the calibration drift
      monitor must trip on a mis-scaled table while staying quiet on
      the honest fit (recalibration re-arms it)
  table_slo              — the SLO scheduler vs the synchronous round
      loop on shared Poisson traces (async must strictly beat sync on
      p95 wall latency AND deadline-miss rate on every mix), plus the
      plan-preserving kill/recover scenario (snapshot -> simulated
      death -> restore must re-plan ZERO cold graphs)
  table_chaos            — fault injection + degraded-mesh survival:
      guarded serving must hold >=99% availability through a NaN
      batch, a corrupted collective, a kernel exception, a latency
      spike, and a device loss — degrading 2 -> 1 devices with ZERO
      cold re-plans (spares pre-warmed) and bounded p95 inflation —
      while the unguarded baseline collapses on the same schedule;
      armed-but-idle injection must be bit-transparent

System benches:
  bench_kernels     — us/call for every kernel family member
  bench_train_step  — smoke-model train-step wall time
  bench_roofline    — reads experiments/dryrun JSONs -> per-cell terms

Output: ``name,us_per_call,derived`` CSV rows on stdout.
"""
from __future__ import annotations

import collections
import json
import shutil
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

ROWS = []

# Wall-clock repetitions per measurement (the --repeat flag); every
# timed table reports the MEDIAN of this many post-warmup runs, so a
# single scheduler hiccup cannot skew a row.
REPEAT = 3


def emit(name: str, us_per_call: float, derived: str = ""):
    ROWS.append((name, us_per_call, derived))
    print(f"{name},{us_per_call:.2f},{derived}")


def _timeit(fn, *args, warmup=1, iters=None) -> float:
    """us/call: ``warmup`` discarded calls, then the median of
    ``iters`` (default: the --repeat setting) timed calls."""
    iters = REPEAT if iters is None else iters
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append(time.perf_counter() - t0)
    return float(np.median(times)) * 1e6


# ---------------------------------------------------------------------------
# Table I — characteristics of the developed IPs (capability matrix)
# ---------------------------------------------------------------------------
def table1_ip_characteristics():
    from repro.core.library import FAMILIES
    print("# Table I — IP library characteristics "
          "(DSP->mxu, logic->vpu, ops/pass, operand ceiling)")
    for fam in FAMILIES.values():
        for ip in fam:
            derived = (f"uses_mxu={int(ip.uses_mxu)};outputs_per_pass="
                       f"{ip.outputs_per_pass};max_bits={ip.max_operand_bits};"
                       f"tags={'|'.join(ip.tags)}")
            emit(f"table1.{ip.name}", 0.0, derived)


# ---------------------------------------------------------------------------
# Table II — resource utilization of the conv IPs (paper's experiment:
# 8-bit fixed point, 3x3 kernel; ZCU104@200MHz -> v5e resource vector)
# ---------------------------------------------------------------------------
def table2_resource_utilization():
    from repro.core.library import CONV2D
    from repro.kernels.conv2d.ops import conv2d, conv2d_dual
    print("# Table II — conv IP resource utilization (paper setup: int8, "
          "3x3 kernel) — vmem/mxu/vpu from footprints, us/call measured "
          "(interpret mode, CPU)")
    rng = np.random.default_rng(0)
    n, h, w, cin, cout = 1, 32, 32, 8, 16
    xa = jnp.asarray(rng.integers(-128, 128, (n, h, w, cin), dtype=np.int8))
    xb = jnp.asarray(rng.integers(-128, 128, (n, h, w, cin), dtype=np.int8))
    wgt = jnp.asarray(rng.integers(-128, 128, (3, 3, cin, cout),
                                   dtype=np.int8))
    for ip in CONV2D:
        fp = ip.footprint(n, h, w, cin, 3, 3, cout, itemsize=1)
        short = ip.name.split(".")[-1]
        if ip.outputs_per_pass == 2:
            us = _timeit(lambda: conv2d_dual(xa, xb, wgt, ip=short))
        else:
            us = _timeit(lambda: conv2d(xa, wgt, ip=short))
        derived = (f"vmem_kib={fp.vmem_bytes/1024:.1f};mxu_passes="
                   f"{fp.mxu_passes};vpu_ops={fp.vpu_ops:.2e};"
                   f"est_cycles={fp.est_cycles:.3e};"
                   f"outputs_per_pass={fp.outputs_per_pass}")
        emit(f"table2.{ip.name}", us, derived)


# ---------------------------------------------------------------------------
# Table III — the PLANNED network vs fixed-IP networks across a budget
# ladder: a 3-layer int8 CNN (conv -> avgpool -> act per layer) is mapped
# by plan_network (one partitioned budget for all 9 sites); each fixed
# baseline runs the same graph with one member per family and is priced
# GENEROUSLY (every site sees the full budget, no partitioning).
# ---------------------------------------------------------------------------
TABLE3_LAYERS = [(8, 16), (16, 32), (32, 32)]   # (cin, cout), 3x3 convs

TABLE3_BASELINES = {
    "fixed_vpu": {"conv2d": "ip1_vpu", "pool2d": "pool_vpu",
                  "activation": "act_vpu"},
    "fixed_mxu": {"conv2d": "ip2_mxu", "pool2d": "pool_im2col",
                  "activation": "act_vpu"},
}


def table3_network_specs(n=2, hw=32):
    # Per-layer sites from the same oracle-derived helper the models use
    # (shapes/dtypes can't drift from what the kernels produce); operands
    # re-enter as int8 each layer (requantized fixed-point network).
    from repro.models.blocks import cnn_block_site_specs
    specs = []
    shape = (n, hw, hw, TABLE3_LAYERS[0][0])
    for li, (cin, cout) in enumerate(TABLE3_LAYERS):
        layer, out = cnn_block_site_specs(
            shape, (3, 3, cin, cout), x_dtype="int8", pool_mode="avg",
            activation="relu6", site=f"layer{li}")
        specs += layer
        shape = out.shape
    return specs


def table3_comparison():
    from repro.core.plan import fixed_network_cost, plan_network
    from repro.core.resources import ResourceBudget
    print("# Table III — resource adaptability, network-level: total est "
          "cycles of the planned network (partitioned budget) vs each "
          "fixed-IP network (full budget per site); x=infeasible")
    budgets = {
        "ample": ResourceBudget(),
        "no_mxu": ResourceBudget(mxu_available=False),
        "vpu_starved": ResourceBudget(vpu_ops_budget=2_000_000),
        "vmem_tight": ResourceBudget(vmem_bytes=6000 * 1024),
        "mxu_modest_vpu_tight": ResourceBudget(vpu_ops_budget=2_000_000,
                                               mxu_passes_budget=12),
    }
    specs = table3_network_specs()
    for bname, budget in budgets.items():
        try:
            # fuse=False: Table III reproduces the paper's per-op
            # selection; the fused-vs-unfused comparison is table_fusion
            plan = plan_network(specs, budget, fuse=False)
            planned = plan.total_cycles
            assign = "|".join(
                f"{s.spec.name.split('.')[0]}.{s.spec.family}:"
                f"{s.ip.name.split('.')[-1]}"
                for s in plan.sites if s.spec.name.startswith("layer0"))
        except ValueError:
            planned, assign = None, "none"
        fixed = {name: fixed_network_cost(specs, members, budget)
                 for name, members in TABLE3_BASELINES.items()}
        beats_all = planned is not None and all(
            v is None or planned < v for v in fixed.values())
        derived = (f"planned={planned:.3e}" if planned is not None
                   else "planned=x")
        for name, v in fixed.items():
            derived += f";{name}={v:.3e}" if v is not None else f";{name}=x"
        derived += (f";planned_best={int(beats_all)};layer0={assign}")
        emit(f"table3.budget_{bname}", 0.0, derived)


# ---------------------------------------------------------------------------
# Table P — the precision ladder, network-level: the same float32 CNN is
# planned twice per budget — once at f32 only, once with a (16, 8) ladder
# on every site — and the ladder plan is EXECUTED end-to-end so every
# lowered site reports its measured error against the family oracles.
# ---------------------------------------------------------------------------
PRECISION_LADDER = (16, 8)


def precision_network_specs(ladder=(), n=2, hw=32):
    from repro.models.blocks import cnn_block_site_specs
    specs = []
    shape = (n, hw, hw, TABLE3_LAYERS[0][0])
    for li, (cin, cout) in enumerate(TABLE3_LAYERS):
        layer, out = cnn_block_site_specs(
            shape, (3, 3, cin, cout), x_dtype="float32", pool_mode="max",
            activation="relu", site=f"layer{li}", ladder=ladder)
        specs += layer
        shape = out.shape
    return specs


def _run_precision_network(weights, x, network, ladder):
    from repro.models.blocks import apply_cnn_block
    report = {}
    y = x
    for li, w in enumerate(weights):
        y = apply_cnn_block({"w": w}, y, pool_mode="max", activation="relu",
                            site=f"layer{li}", network=network,
                            ladder=ladder, quant_report=report)
    return y, report


def table_precision():
    from repro.core.plan import plan_network
    from repro.core.resources import ResourceBudget
    from repro.quant.report import max_rel_error
    print("# Table P — precision ladder: f32-only vs ladder-planned "
          "network per budget; cycles planned, us measured (interpret "
          "mode), err = max per-site rel error of the executed ladder "
          "plan vs the f32 oracles; x=infeasible")
    budgets = {
        # ladder never engages; plans identical
        "ample": ResourceBudget(),
        # partitioned slices push sites down the ladder; the lowered
        # plan is strictly CHEAPER (narrower operands = less traffic)
        # while f32-only still fits
        "vmem_4000KiB": ResourceBudget(vmem_bytes=4000 * 1024),
        # f32-only is infeasible; only the ladder plan exists
        "vmem_2400KiB": ResourceBudget(vmem_bytes=2400 * 1024),
        # below every rung: both plans infeasible (honest envelope end)
        "vmem_1600KiB": ResourceBudget(vmem_bytes=1600 * 1024),
    }
    rng = np.random.default_rng(0)
    weights = [jnp.asarray(rng.normal(0, (3 * 3 * cin) ** -0.5,
                                      (3, 3, cin, cout)).astype(np.float32))
               for cin, cout in TABLE3_LAYERS]
    x = jnp.asarray(rng.normal(size=(2, 32, 32, 8)).astype(np.float32))
    specs_f32 = precision_network_specs()
    specs_lad = precision_network_specs(PRECISION_LADDER)
    for bname, budget in budgets.items():
        # fuse=False keeps this the pure precision-ladder comparison
        # (and the committed trajectory comparable); fusion x ladder
        # interplay is table_fusion's job
        try:
            f32_cycles = plan_network(specs_f32, budget,
                                      fuse=False).total_cycles
        except ValueError:
            f32_cycles = None
        try:
            lad_plan = plan_network(specs_lad, budget, fuse=False)
        except ValueError:
            lad_plan = None
        if lad_plan is None:
            emit(f"table_precision.budget_{bname}", 0.0,
                 ("f32=x;" if f32_cycles is None
                  else f"f32={f32_cycles:.3e};") + "ladder=x")
            continue
        us = _timeit(lambda: _run_precision_network(
            weights, x, lad_plan, PRECISION_LADDER)[0])
        _, report = _run_precision_network(weights, x, lad_plan,
                                           PRECISION_LADDER)
        lowered = lad_plan.lowered_sites()
        bits = "|".join(f"{s.spec.name}:{s.precision_bits}"
                        for s in lowered) or "none"
        err = max_rel_error(report)
        wins = f32_cycles is None or lad_plan.total_cycles < f32_cycles
        derived = (("f32=x" if f32_cycles is None
                    else f"f32={f32_cycles:.3e}")
                   + f";ladder={lad_plan.total_cycles:.3e}"
                   + f";lowered={len(lowered)};bits={bits}"
                   + f";max_rel_err={err:.3e}"
                   + f";err_ok={int(err <= 5e-2)}"
                   + f";ladder_wins={int(wins)}")
        emit(f"table_precision.budget_{bname}", us, derived)


# ---------------------------------------------------------------------------
# Table F — fused CNN blocks vs the unfused three-launch chain: the same
# ladder-equipped float32 CNN is planned twice per budget (plan_network
# with and without fuse=True) and BOTH plans are executed end-to-end, so
# each row reports planned est-cycles (where the counted DMA-byte saving
# lands), launch count (3 -> 1 per fused block), measured wall-clock
# (interpret-mode median of --repeat runs), and the fused sites'
# measured error against the composite f32 oracle.
# ---------------------------------------------------------------------------
def table_fusion():
    from repro.core.plan import clear_plan_cache, plan_network
    from repro.core.resources import ResourceBudget
    from repro.quant.report import max_rel_error
    print("# Table F — fusion: fused conv->pool->act blocks vs the "
          "unfused three-launch chain per budget; cycles planned, "
          "launches counted, us measured (interpret mode, median of "
          f"{REPEAT}), err = max per-site rel error of the executed "
          "fused plan vs the f32 oracles; x=infeasible")
    budgets = {
        "ample": ResourceBudget(),
        "no_mxu": ResourceBudget(mxu_available=False),
        # the unfused chain lowers its pool/act sites; every fused site
        # still fits at f32
        "vmem_5000KiB": ResourceBudget(vmem_bytes=5000 * 1024),
        # one fused site descends to bf16
        "vmem_4000KiB": ResourceBudget(vmem_bytes=4000 * 1024),
        # tight enough that every fused site descends to the int8 rung
        # (the in-register-rescale path) and must stay within the error
        # bound; the unfused chain no longer fits at all
        "vmem_1200KiB": ResourceBudget(vmem_bytes=1200 * 1024),
        "vpu_starved": ResourceBudget(vpu_ops_budget=2_000_000),
    }
    rng = np.random.default_rng(0)
    weights = [jnp.asarray(rng.normal(0, (3 * 3 * cin) ** -0.5,
                                      (3, 3, cin, cout)).astype(np.float32))
               for cin, cout in TABLE3_LAYERS]
    x = jnp.asarray(rng.normal(size=(2, 32, 32, 8)).astype(np.float32))
    specs = precision_network_specs(PRECISION_LADDER)
    for bname, budget in budgets.items():
        clear_plan_cache()
        plans = {}
        for arm, fuse in (("unfused", False), ("fused", True)):
            try:
                plans[arm] = plan_network(specs, budget, fuse=fuse)
            except ValueError:
                plans[arm] = None
        unf, fus = plans["unfused"], plans["fused"]
        if fus is None:
            emit(f"table_fusion.budget_{bname}", 0.0,
                 ("unfused=x;" if unf is None
                  else f"unfused={unf.total_cycles:.3e};") + "fused=x")
            continue
        us_fused = _timeit(lambda: _run_precision_network(
            weights, x, fus, PRECISION_LADDER)[0])
        _, report = _run_precision_network(weights, x, fus,
                                           PRECISION_LADDER)
        us_unfused = (None if unf is None else _timeit(
            lambda: _run_precision_network(weights, x, unf,
                                           PRECISION_LADDER)[0]))
        fused_sites = [s for s in fus.sites
                       if s.spec.family == "cnn_fused"]
        err = max_rel_error(report, lowered_only=False)
        # Modeled and measured verdicts are SEPARATE columns: the old
        # fused_wins/never_worse flags were derived from est-cycles only,
        # so the bench could self-certify a "win" while wall-clock said
        # otherwise (the calibration layer exists because they disagree —
        # see table_calibration).
        modeled = unf is None or fus.total_cycles < unf.total_cycles
        measured = us_unfused is None or us_fused < us_unfused
        bits = "|".join(f"{s.spec.name}:{s.precision_bits}"
                        for s in fused_sites) or "none"
        derived = (("unfused=x" if unf is None
                    else f"unfused={unf.total_cycles:.3e}")
                   + f";fused={fus.total_cycles:.3e}"
                   + (";launches_unfused=x" if unf is None
                      else f";launches_unfused={unf.total_launches}")
                   + f";launches_fused={fus.total_launches}"
                   + f";fused_sites={len(fused_sites)};bits={bits}"
                   + (";us_unfused=x" if us_unfused is None
                      else f";us_unfused={us_unfused:.1f}")
                   + f";us_fused={us_fused:.1f}"
                   + f";max_rel_err={err:.3e}"
                   + f";err_ok={int(err <= 5e-2)}"
                   + f";modeled_wins={int(modeled)}"
                   + f";measured_wins={int(measured)}")
        emit(f"table_fusion.budget_{bname}", us_fused, derived)


# ---------------------------------------------------------------------------
# Table C — the measurement-calibrated cost model closing the loop that
# Table F exposed: fused plans were MODELED cheaper on every budget while
# MEASURED slower on some.  A warmup pass measures every distinct planned
# site standalone (core.calibrate_cost.collect_plan_samples), an affine
# model is fit per executed member, and the planner re-decides fusion
# under calibration=: the calibrated fused-vs-unfused ranking must match
# measured wall-clock on EVERY budget of the fusion ladder, and any
# budget whose stopwatch prefers unfused must now PLAN unfused (both
# asserted; which budgets those are is a property of the host — on the
# seed-trajectory host, vpu_starved and no_mxu measured fused slower).
# ---------------------------------------------------------------------------
def table_calibration(smoke: bool = False):
    from repro.core.calibrate_cost import (CalibrationTable,
                                           collect_plan_samples)
    from repro.core.plan import clear_plan_cache, plan_network
    from repro.core.resources import ResourceBudget
    print("# Table C — calibrated cost model: per-site warmup samples -> "
          "affine fits -> the planner's fused-vs-unfused choice must "
          "match measured wall-clock on every fusion-ladder budget "
          "(interpret mode, median of runs); x=infeasible")
    budgets = {
        "ample": ResourceBudget(),
        "no_mxu": ResourceBudget(mxu_available=False),
        "vmem_5000KiB": ResourceBudget(vmem_bytes=5000 * 1024),
        "vmem_4000KiB": ResourceBudget(vmem_bytes=4000 * 1024),
        # no int8 row: where both arms fit and a fused site is on the
        # int8 rung (2800 KiB), the arms differ in one site and their
        # calibrated costs by ~2%, so the stopwatch's verdict there is
        # host noise; the int8
        # fused rung is exercised by table_fusion at 1200 KiB
        "vpu_starved": ResourceBudget(vpu_ops_budget=2_000_000),
    }
    rng = np.random.default_rng(0)
    weights = [jnp.asarray(rng.normal(0, (3 * 3 * cin) ** -0.5,
                                      (3, 3, cin, cout)).astype(np.float32))
               for cin, cout in TABLE3_LAYERS]
    x = jnp.asarray(rng.normal(size=(2, 32, 32, 8)).astype(np.float32))
    specs = precision_network_specs(PRECISION_LADDER)
    repeat = 2 if smoke else REPEAT
    # Phase 1 — warmup sampling: plan both arms of every budget with the
    # ANALYTICAL model and measure each distinct planned site standalone.
    # Three layer shapes per member give each fit >= 3 footprint points.
    clear_plan_cache()
    arm_plans = {}
    for bname, budget in budgets.items():
        plans = {}
        for arm, fuse in (("unfused", False), ("fused", True)):
            try:
                plans[arm] = plan_network(specs, budget, fuse=fuse)
            except ValueError:
                plans[arm] = None
        arm_plans[bname] = plans
    table = collect_plan_samples(
        [p for plans in arm_plans.values() for p in plans.values()],
        repeat=repeat).fit()
    # Acceptance: the table must round-trip through JSON bit-exactly.
    assert CalibrationTable.from_json(table.to_json()).to_json() \
        == table.to_json(), "CalibrationTable JSON round-trip not bit-exact"
    emit("table_calibration.table", 0.0,
         f"samples={table.sample_count()};members_fit={len(table.fits)};"
         f"fingerprint={table.fingerprint()}")
    # Phase 2 — per budget: measure both arms end-to-end, then ask the
    # CALIBRATED planner; its ranking must agree with the stopwatch.
    mismatches = []
    for bname, budget in budgets.items():
        unf, fus = arm_plans[bname]["unfused"], arm_plans[bname]["fused"]
        if unf is None or fus is None:
            emit(f"table_calibration.budget_{bname}", 0.0,
                 ("unfused=x;" if unf is None else "") +
                 ("fused=x" if fus is None else ""))
            continue
        us_unfused = _timeit(lambda: _run_precision_network(
            weights, x, unf, PRECISION_LADDER)[0], iters=repeat)
        us_fused = _timeit(lambda: _run_precision_network(
            weights, x, fus, PRECISION_LADDER)[0], iters=repeat)
        cal_unf = unf.calibrated_cycles(table)
        cal_fus = fus.calibrated_cycles(table)
        cal_plan = plan_network(specs, budget, fuse=True, calibration=table)
        plans_fused = sum(1 for s in cal_plan.sites
                          if s.spec.family == "cnn_fused")
        modeled_pref = fus.total_cycles < unf.total_cycles
        calibrated_pref = cal_fus < cal_unf
        measured_pref = us_fused < us_unfused
        match = calibrated_pref == measured_pref
        if not match:
            mismatches.append(bname)
        derived = (f"us_unfused={us_unfused:.1f};us_fused={us_fused:.1f}"
                   f";cal_unfused={cal_unf:.3e};cal_fused={cal_fus:.3e}"
                   f";modeled_prefers_fused={int(modeled_pref)}"
                   f";calibrated_prefers_fused={int(calibrated_pref)}"
                   f";measured_prefers_fused={int(measured_pref)}"
                   f";plans_fused_sites={plans_fused}"
                   f";ranking_match={int(match)}")
        emit(f"table_calibration.budget_{bname}", us_fused, derived)
        # The flip the calibration layer exists for: wherever the
        # stopwatch prefers the unfused chain (e.g. vpu_starved on the
        # host that produced the seed BENCH_table_fusion.json), the
        # calibrated planner must actually plan it unfused — the
        # analytical model fused everywhere regardless.
        if not measured_pref:
            assert plans_fused == 0, (
                f"budget_{bname}: measured wall-clock prefers unfused "
                f"but the calibrated planner kept {plans_fused} fused "
                f"sites")
    assert not mismatches, (
        f"calibrated fused-vs-unfused ranking disagrees with measured "
        f"wall-clock on: {mismatches}")


# ---------------------------------------------------------------------------
# Table S — the serving runtime: one constrained device, two tenants,
# skewed load.  The same request trace is replayed against a static even
# budget split and the demand arbiter; the arbiter must buy the heavy
# tenant the fast (VPU-hungry) conv member while the squeezed light
# tenant degrades down the precision ladder instead of failing.  The
# device is constrained on BOTH axes: vpu_ops drives the member choice,
# and vmem forces the squeezed tenant's fused block (serving plans fuse
# by default) below f32 — the per-op tanh squeeze the table originally
# used no longer bites once conv+pool+act share one VMEM-resident tile.
# Latency is est-cycles — the planner's own cost model — so policies
# compare without interpret-mode wall-clock noise.
# ---------------------------------------------------------------------------
SERVING_DEVICE_VPU_OPS = 15_000_000
SERVING_DEVICE_VMEM = 4500 * 1024
SERVING_WAVES = 3


def _serving_tenants():
    import jax
    from repro.models.frontends import init_cnn_frontend
    heavy = init_cnn_frontend(jax.random.PRNGKey(0), channels=(8, 16),
                              d_model=32)
    light = init_cnn_frontend(jax.random.PRNGKey(1), channels=(6, 12),
                              d_model=16)
    return heavy, light


def _run_serving(policy: str, n_heavy: int, n_light: int, *,
                 waves: int = SERVING_WAVES):
    """Replay one skewed trace under one policy; fresh caches so each
    policy models an independent serving process."""
    from repro.core.plan import clear_plan_cache
    from repro.core.resources import ResourceBudget
    from repro.runtime import AdaptiveServer

    clear_plan_cache()
    device = ResourceBudget(vpu_ops_budget=SERVING_DEVICE_VPU_OPS,
                            vmem_bytes=SERVING_DEVICE_VMEM)
    heavy_p, light_p = _serving_tenants()
    srv = AdaptiveServer(device, policy=policy, max_batch=4)
    srv.register("vision-heavy", heavy_p, (32, 32, 8))
    # the squeeze target: the light tenant's ~7% vmem slice cannot hold
    # its fused blocks at f32, so the ladder lowers them
    srv.register("edge-light", light_p, (24, 24, 6), activation="tanh",
                 ladder=(16, 8), measure_quant=True)
    rng = np.random.default_rng(0)
    latencies = []
    t = 0.0
    for _ in range(waves):
        for _ in range(n_heavy):
            srv.submit("vision-heavy",
                       rng.normal(size=(32, 32, 8)).astype(np.float32), at=t)
        for _ in range(n_light):
            srv.submit("edge-light",
                       rng.normal(size=(24, 24, 6)).astype(np.float32), at=t)
        latencies += [c.latency for c in srv.step()]
        t = srv.clock
    return float(np.percentile(latencies, 95)), srv.telemetry()


def table_serving(smoke: bool = False):
    print("# Table S — serving: static even split vs demand-arbitrated "
          "budgets on one constrained device (vpu_ops_budget="
          f"{SERVING_DEVICE_VPU_OPS}, vmem={SERVING_DEVICE_VMEM >> 10}"
          "KiB); p95 in est-cycles; the "
          "squeezed tenant must serve at a lowered rung within the 5e-2 "
          "error bound")
    mixes = {"skew_10to2": (10, 2)}
    if not smoke:
        mixes = {"skew_4to2": (4, 2), **mixes, "skew_16to2": (16, 2)}
    for mname, (nh, nl) in mixes.items():
        per_policy = {}
        for policy in ("static", "demand"):
            per_policy[policy] = _run_serving(policy, nh, nl)
        static_p95, _ = per_policy["static"]
        arb_p95, arb_tel = per_policy["demand"]
        light = arb_tel["edge-light"]
        heavy = arb_tel["vision-heavy"]
        lowered_bits = sorted(b for b in light["precision_mix"] if b < 32)
        err = light["max_quant_rel_err"]
        derived = (f"static_p95={static_p95:.3e};arb_p95={arb_p95:.3e}"
                   f";arb_beats_static={int(arb_p95 < static_p95)}"
                   f";heavy_grant={heavy['granted_fraction']:.3f}"
                   f";light_grant={light['granted_fraction']:.3f}"
                   f";squeezed=edge-light"
                   f";lowered_bits={'|'.join(map(str, lowered_bits)) or 'none'}"
                   f";lowered_frac={light['lowered_fraction']:.2f}"
                   f";max_rel_err={err:.3e};err_ok={int(err <= 5e-2)}"
                   f";occupancy={heavy['batch_occupancy']:.2f}"
                   f";cache_hit_rate={heavy['plan_cache_hit_rate']:.2f}")
        emit(f"table_serving.{mname}", 0.0, derived)


# ---------------------------------------------------------------------------
# Table M — mesh-sharded planning: the collective-priced partitioner must
# (a) WIN where splitting pays: a conv whose single-device plan is gated
#     onto the VPU member (mxu_passes_budget=7 gates ip2_mxu); the
#     2-device batch split halves the per-device footprint, and the
#     sharded execution must beat the best
#     1-device plan in BOTH modeled est-cycles and measured wall-clock;
# (b) REFUSE where it doesn't: a tiny 1x1 conv whose collectives dwarf
#     its compute must plan at degree=1, and the forced-shard
#     counterfactual must MEASURE slower — the refusal asserted from the
#     stopwatch, not just the model.
# Runs in a subprocess under XLA_FLAGS=--xla_force_host_platform_
# device_count=2 (JAX fixes its device count at import); see
# benchmarks/_mesh_child.py for the workloads.
# ---------------------------------------------------------------------------
def table_mesh(smoke: bool = False):
    import os
    import subprocess
    import sys
    print("# Table M — mesh sharding: 2-device planned split vs best "
          "1-device plan (win case) and degree=1 refusal vs forced "
          "shard (refusal case); modeled cycles AND measured us, both "
          "asserted; host mesh via forced device count")
    child = Path(__file__).resolve().parent / "_mesh_child.py"
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    # the parent holds the accelerator: the forced-host child runs on CPU
    env["JAX_PLATFORMS"] = "cpu"
    repeat = 2 if smoke else REPEAT
    proc = subprocess.run(
        [sys.executable, str(child), str(repeat)], env=env,
        capture_output=True, text=True, timeout=900)
    if proc.returncode != 0:
        raise RuntimeError(f"mesh child failed:\n{proc.stderr[-4000:]}")
    rec = json.loads(proc.stdout.splitlines()[-1])
    assert rec["devices"] == 2, \
        f"forced host mesh did not take: {rec['devices']} device(s)"
    win, ref = rec["win"], rec["refusal"]
    # (a) the split must be chosen, modeled cheaper, measured faster,
    # and numerically exact (batch sharding is bit-identical for f32)
    assert win["shard_degree"] == 2 and win["shard_axis"] == "batch", \
        f"planner did not shard the win case: {win}"
    assert win["est_2dev"] < win["est_1dev"], \
        f"modeled: sharded plan not cheaper: {win}"
    assert win["us_2dev"] < win["us_1dev"], \
        f"measured: sharded plan not faster: {win}"
    assert win["bit_identical"], "sharded result != replicated result"
    emit("table_mesh.split_wins", win["us_2dev"],
         f"ip_1dev={win['ip_1dev'].split('.')[-1]}"
         f";ip_2dev={win['ip_2dev'].split('.')[-1]}"
         f";axis={win['shard_axis']}x{win['shard_degree']}"
         f";est_1dev={win['est_1dev']:.3e};est_2dev={win['est_2dev']:.3e}"
         f";comm={win['comm_2dev']:.3e}"
         f";us_1dev={win['us_1dev']:.1f};us_2dev={win['us_2dev']:.1f}"
         f";modeled_wins=1;measured_wins=1;bit_identical=1;platform=cpu")
    # (b) the refusal must hold in the model AND in the stopwatch
    assert ref["shard_degree"] == 1, \
        f"planner sharded the refusal case: {ref}"
    assert ref["comm_forced"] > ref["est_chosen"], \
        f"refusal case does not stress collectives: {ref}"
    assert ref["us_forced"] > ref["us_chosen"], \
        f"measured: forced shard was not slower: {ref}"
    emit("table_mesh.refuses", ref["us_chosen"],
         f"degree=1;est_chosen={ref['est_chosen']:.3e}"
         f";comm_forced={ref['comm_forced']:.3e}"
         f";us_chosen={ref['us_chosen']:.1f}"
         f";us_forced={ref['us_forced']:.1f}"
         f";refusal_right=1;platform=cpu")


# ---------------------------------------------------------------------------
# Table O — cross-layer observability (src/repro/obs): four asserted
# phases.
# (a) AUDIT: every site whose constrained-budget choice moved off the
#     ample-budget first choice must carry a concrete, numbered
#     rejection reason in the plan audit (NetworkPlan.explain());
# (b) TRACE: a serving cycle under the JAX profiler must put plan,
#     dispatch and arbiter spans on the profiler's timeline (written
#     to experiments/obs/trace/ with a perfetto_trace.json.gz — load it
#     in Perfetto);
# (c) OVERHEAD: the same serving trace with the profiler on must stay
#     within a bounded factor of the profiler-off run (the disabled
#     path is allocation-free; the enabled path is one annotation per
#     span);
# (d) DRIFT: a calibration table fit on honest measurements must stay
#     quiet under the drift monitor while the same measurements against
#     a mis-scaled copy of the table must trip it — and recalibrate()
#     must refit the bad table (new fingerprint) back to quiet.
# Also writes the Prometheus exposition of the traced serving process
# to experiments/obs/metrics.prom.
# ---------------------------------------------------------------------------
OBS_DRIFT_SCALE = 8.0          # the mis-scaled table's coefficient factor
OBS_OVERHEAD_BOUND = 2.0       # profiler-on / profiler-off wall-clock ceiling


def _obs_serving_cycle(n_heavy=4, n_light=2):
    """One small serving trace (fresh caches, demand policy); returns
    (server, wall-clock seconds)."""
    from repro.core.plan import clear_plan_cache
    from repro.core.resources import ResourceBudget
    from repro.runtime import AdaptiveServer

    clear_plan_cache()
    device = ResourceBudget(vpu_ops_budget=SERVING_DEVICE_VPU_OPS,
                            vmem_bytes=SERVING_DEVICE_VMEM)
    heavy_p, light_p = _serving_tenants()
    srv = AdaptiveServer(device, policy="demand", max_batch=4)
    srv.register("vision-heavy", heavy_p, (32, 32, 8))
    srv.register("edge-light", light_p, (24, 24, 6), activation="tanh",
                 ladder=(16, 8))
    rng = np.random.default_rng(0)
    t0 = time.perf_counter()
    for _ in range(2):
        for _ in range(n_heavy):
            srv.submit("vision-heavy",
                       rng.normal(size=(32, 32, 8)).astype(np.float32))
        for _ in range(n_light):
            srv.submit("edge-light",
                       rng.normal(size=(24, 24, 6)).astype(np.float32))
        srv.step()
    return srv, time.perf_counter() - t0


def table_obs(smoke: bool = False):
    from repro.core.calibrate_cost import (collect_plan_samples,
                                           measure_planned_site,
                                           member_key)
    from repro.core.plan import clear_plan_cache, plan_network
    from repro.core.resources import ResourceBudget
    from repro.obs import DriftMonitor, mis_scaled_table
    print("# Table O — observability: plan audits name concrete "
          "rejection reasons; a serving cycle under the profiler puts "
          "plan/dispatch/arbiter spans on its timeline within "
          f"{OBS_OVERHEAD_BOUND}x of the untraced run; the drift "
          "monitor stays quiet on the honest calibration table and "
          f"trips on a {OBS_DRIFT_SCALE}x mis-scaled copy, and "
          "recalibrate() refits it quiet")
    out_dir = Path(__file__).resolve().parent.parent / "experiments" / "obs"
    out_dir.mkdir(parents=True, exist_ok=True)
    repeat = 2 if smoke else REPEAT

    # -- (a) plan decision audit -------------------------------------------
    clear_plan_cache()
    specs = precision_network_specs(PRECISION_LADDER)
    ample = plan_network(specs, ResourceBudget())
    first_choice = {s.spec.name: (s.ip.name, s.precision_bits)
                    for s in ample.sites}
    budgets = {
        "vmem_2800KiB": ResourceBudget(vmem_bytes=2800 * 1024),
        "vpu_starved": ResourceBudget(vpu_ops_budget=2_000_000),
        "no_mxu": ResourceBudget(mxu_available=False),
    }
    non_first, explained = 0, 0
    for bname, budget in budgets.items():
        plan = plan_network(specs, budget)
        assert plan.audit is not None, f"{bname}: cold plan has no audit"
        for site in plan.sites:
            choice = (site.ip.name, site.precision_bits)
            was_first = first_choice.get(site.spec.name) == choice
            lowered = site.precision_bits < site.spec.native_bits
            if was_first and not lowered:
                continue
            non_first += 1
            reasons = plan.audit.site(site.spec.name).rejection_reasons()
            assert reasons and any(c.isdigit()
                                   for r in reasons for c in r), (
                f"{bname}/{site.spec.name}: moved off the first choice "
                f"{first_choice.get(site.spec.name)} -> {choice} with no "
                f"concrete rejection reason; explain():\n{plan.explain()}")
            explained += 1
    assert non_first > 0, "constrained budgets moved no site choices"
    emit("table_obs.audit", 0.0,
         f"non_first_choice={non_first};explained={explained};"
         f"audit_ok={int(non_first == explained)}")

    # -- (b) + (c) traced serving cycle, then the overhead bound -----------
    _, base_s = _obs_serving_cycle()          # warm compile, untraced
    _, off_s = _obs_serving_cycle()
    trace_dir = out_dir / "trace"
    shutil.rmtree(trace_dir, ignore_errors=True)
    with jax.profiler.trace(str(trace_dir), create_perfetto_trace=True):
        srv, on_s = _obs_serving_cycle()
    metrics_text = srv.metrics().render()
    (xplane,) = trace_dir.glob("**/*.xplane.pb")
    names = collections.Counter(
        ev.name for plane in jax.profiler.ProfileData.from_file(
            str(xplane)).planes if plane.name.startswith("/host:")
        for line in plane.lines for ev in line.events)
    kinds = {"plan": ("plan_network", "replan", "serve.plan"),
             "dispatch": ("serve.dispatch",),
             "arbiter": ("arbiter.split",)}
    missing = {k for k, spans in kinds.items()
               if not any(names[n] for n in spans)}
    assert not missing, f"trace is missing spans of: {missing}"
    assert list(trace_dir.glob("**/perfetto_trace.json.gz")), \
        "the profiler wrote no Perfetto timeline"
    (out_dir / "metrics.prom").write_text(metrics_text)
    program = sorted(n for n in names
                     if n.startswith(("serve.", "arbiter."))
                     or n in ("plan_network", "replan", "select"))
    spans = sum(names[n] for n in program)
    ratio = on_s / max(off_s, 1e-9)
    overhead_ok = ratio < OBS_OVERHEAD_BOUND
    assert overhead_ok, (
        f"tracing overhead {ratio:.2f}x exceeds the "
        f"{OBS_OVERHEAD_BOUND}x bound (off={off_s * 1e6:.0f}us, "
        f"on={on_s * 1e6:.0f}us)")
    emit("table_obs.trace", on_s * 1e6,
         f"trace_valid=1;spans={spans};names={'|'.join(program)}"
         f";off_us={off_s * 1e6:.0f};on_us={on_s * 1e6:.0f}"
         f";overhead_x={ratio:.2f};overhead_ok={int(overhead_ok)}")

    # -- (d) calibration drift --------------------------------------------
    clear_plan_cache()
    plan = plan_network(specs, ResourceBudget())
    # discard one warm pass per site first: the fit and the monitor must
    # observe the same warm regime, or still-warming early samples skew
    # the fit and read as honest-table drift
    for site in plan.sites:
        measure_planned_site(site, repeat=1)
    table = collect_plan_samples([plan], repeat=repeat).fit()
    bad = mis_scaled_table(table, OBS_DRIFT_SCALE)
    # threshold sits between interpret-mode timing noise (honest err
    # ~0.3-0.8 on a loaded CI box) and the 8x mis-scale (err ~7)
    honest_mon = DriftMonitor(table, threshold=2.0, min_observations=3)
    bad_mon = DriftMonitor(bad, threshold=2.0, min_observations=3)
    observations = []
    for site in plan.sites:
        member = member_key(site.ip.name, site.precision_bits,
                            site.spec.native_bits)
        us = measure_planned_site(site, repeat=repeat)
        observations.append((member, site.footprint, us))
        honest_mon.observe(member, site.footprint, us)
        bad_mon.observe(member, site.footprint, us)
    assert not honest_mon.drifted, (
        f"honest table tripped the drift monitor: "
        f"{honest_mon.snapshot()}")
    assert bad_mon.drifted, (
        f"{OBS_DRIFT_SCALE}x mis-scaled table did not trip: "
        f"{bad_mon.snapshot()}")
    old_fp = bad.fingerprint()
    new_fp = bad_mon.recalibrate()
    assert new_fp != old_fp, "recalibrate() did not move the fingerprint"
    for member, fp, us in observations:
        bad_mon.observe(member, fp, us)
    recal_ok = not bad_mon.drifted
    assert recal_ok, (
        f"recalibrated table still drifts: {bad_mon.snapshot()}")
    emit("table_obs.drift", 0.0,
         f"drift_honest={int(honest_mon.drifted)}"
         f";drift_perturbed=1;scale={OBS_DRIFT_SCALE}"
         f";honest_err={honest_mon.mean_rel_error:.3f}"
         f";recalibrated_ok={int(recal_ok)}")


# ---------------------------------------------------------------------------
# Kernel microbenches
# ---------------------------------------------------------------------------
def bench_kernels():
    from repro.kernels.matmul.ops import matmul, matmul_dual
    from repro.kernels.attention.flash import flash_attention
    from repro.kernels.attention.decode import flash_decode
    print("# kernel microbenches (interpret mode on CPU — correctness "
          "vehicles; TPU perf comes from the dry-run roofline)")
    rng = np.random.default_rng(0)
    a = jnp.asarray(rng.integers(-128, 128, (256, 256), dtype=np.int8))
    b = jnp.asarray(rng.integers(-128, 128, (256, 256), dtype=np.int8))
    emit("kernel.mm_mxu_int8_256", _timeit(
        lambda: matmul(a, b, ip="mm_mxu", bm=128, bn=128, bk=128)),
        "m=k=n=256")
    a2 = jnp.asarray(rng.integers(-128, 128, (256, 256), dtype=np.int8))
    emit("kernel.mm_dual_shared_256", _timeit(
        lambda: matmul_dual(a, a2, b, ip="mm_dual_shared",
                            bm=128, bn=128, bk=128)),
        "two streams, one weight fetch")
    q = jnp.asarray(rng.normal(size=(1, 4, 128, 32)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(1, 2, 128, 32)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(1, 2, 128, 32)).astype(np.float32))
    emit("kernel.flash_attn_128", _timeit(
        lambda: flash_attention(q, k, v, bq=64, bk=64)), "S=128 GQA2")
    qd = jnp.asarray(rng.normal(size=(1, 4, 1, 32)).astype(np.float32))
    kd = jnp.asarray(rng.normal(size=(1, 2, 512, 32)).astype(np.float32))
    emit("kernel.flash_decode_512", _timeit(
        lambda: flash_decode(qd, kd, kd, bk=128)), "cache=512")


def bench_quantize():
    """Fixed-point (paper discipline) on the LM path: w8a8 accuracy +
    the wire/HBM savings it buys."""
    from repro.quant import (int8_matmul, quantization_error,
                             quantize_weights)
    print("# w8a8 fixed-point path (paper's 8-bit discipline on matmul)")
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(64, 512)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(512, 256)).astype(np.float32))
    wq = quantize_weights(w)
    us = _timeit(lambda: int8_matmul(x, wq))
    y_q = int8_matmul(x, wq)
    y_f = jnp.einsum("mk,kn->mn", x, w)
    rel = float(jnp.linalg.norm(y_q - y_f) / jnp.linalg.norm(y_f))
    emit("quantize.w8a8_matmul", us,
         f"rel_err={rel:.4f};weight_bytes=0.25x;werr="
         f"{quantization_error(w):.4f}")


def bench_train_step():
    from repro.configs import get_config
    from repro.configs.base import ShapeConfig
    from repro.models import api
    from repro.models.frontends import make_inputs
    from repro.optim.adamw import AdamWConfig
    print("# train-step wall time (smoke configs, CPU)")
    shape = ShapeConfig("bench", 64, 4, "train")
    opt = AdamWConfig()
    for arch in ("olmo-1b", "dbrx-132b", "rwkv6-3b"):
        cfg = get_config(arch, smoke=True)
        batch = make_inputs(cfg, shape, abstract=False)
        state = api.init_train_state(cfg, opt, jax.random.PRNGKey(0))
        fn = jax.jit(lambda s, bt: api.train_step(cfg, opt, s, bt))
        us = _timeit(fn, state, batch, warmup=1, iters=3)
        emit(f"train_step.{arch}-smoke", us, "batch=4 seq=64")


# ---------------------------------------------------------------------------
# Roofline summary (reads the dry-run artifacts)
# ---------------------------------------------------------------------------
def bench_roofline():
    out = Path("experiments/dryrun")
    if not out.exists():
        print("# roofline: experiments/dryrun missing — run "
              "`python -m repro.launch.dryrun` first")
        return
    print("# roofline per (arch x shape) from the single-pod dry-run "
          "(multi-pod cells are compile-proofs, not calibrated rooflines) "
          "(derived=dominant;fraction;terms in ms)")
    for f in sorted(out.glob("*__single.json")):
        rec = json.loads(f.read_text())
        if rec.get("status") != "ok" or rec.get("tag", "baseline") != "baseline":
            continue
        r = rec["roofline"]
        derived = (f"dom={r['dominant']};frac={r['roofline_fraction']:.3f};"
                   f"tc={r['t_compute_s']*1e3:.2f}ms;"
                   f"tm={r['t_memory_s']*1e3:.2f}ms;"
                   f"tcoll={r['t_collective_s']*1e3:.2f}ms;"
                   f"useful={r['useful_flops_ratio']:.2f}")
        emit(f"roofline.{rec['cell']}", 0.0, derived)


# ---------------------------------------------------------------------------
# Table SLO — the SLO scheduler vs the synchronous loop, plus
# plan-preserving recovery.
#
# Both arms replay the SAME Poisson trace (arrival times in est-cycles;
# admission happens when each loop's model clock reaches the arrival,
# so neither arm sleeps) and are judged on the dual-clock rule: the
# modeled clock orders admissions, the monotonic wall clock judges
# deadlines.  The sync arm is round-synchronous — admissions between
# rounds, results stamped at round end (that IS the round-based serving
# contract the scheduler replaces); the async arm stamps per launch.
# Deadlines are calibrated from a measured warm batch wall time
# (host-adaptive), so the assertions hold across machine speeds.
#
# Asserted per mix: async p95 wall latency < sync, async deadline-miss
# rate < sync.  Asserted once: kill/recover replans ZERO cold graphs
# (``STATS.plan_misses`` delta across restore + first post-crash wave).
# ---------------------------------------------------------------------------
# Tight enough that the sync loop's structural light latency (rest of
# the in-flight round + one full heavy round ahead of it in FIFO bucket
# order, ~2.5-3x) sits ABOVE it while the priority scheduler's
# (~1-1.6x) sits well below — the miss-rate comparison then separates
# the policies structurally, not by trace luck.
SLO_LIGHT_DEADLINE_UNITS = 2.0  # x warm-batch wall time (tight)
SLO_HEAVY_DEADLINE_UNITS = 30.0  # x warm-batch wall time (loose)
# Heavy arrivals run slightly past service capacity (~4 per warm-batch
# unit at max_batch=4), so a heavy backlog persists through the trace:
# the sync loop drains the WHOLE heavy bucket before the light one each
# round, making light wait behind the full backlog, while the
# scheduler's per-launch priority pick serves light between heavy
# batches.  Heavy's own deadline is loose enough (30x) that the backlog
# never threatens it in either arm.
SLO_HEAVY_MEAN_IAT_UNITS = 1 / 4.5   # heavy Poisson mean inter-arrival
SLO_LIGHT_MEAN_IAT_UNITS = 1.0       # light Poisson mean inter-arrival


def _slo_deployment(slo_pressure=0.0):
    """The canonical two-tenant constrained device.  Does NOT clear the
    plan cache: the mix comparison benches the steady-state (warm)
    serving regime — the cold-restart cost is exactly what the recovery
    scenario measures separately."""
    from repro.core.resources import ResourceBudget
    from repro.runtime import AdaptiveServer

    device = ResourceBudget(vpu_ops_budget=SERVING_DEVICE_VPU_OPS,
                            vmem_bytes=SERVING_DEVICE_VMEM)
    heavy_p, light_p = _serving_tenants()
    # grant_quantum bounds the budget-slice key space so the warmup
    # replay's plan-cache entries cover the measured replay's grants:
    # without it every EWMA fold mints a fresh fractional budget and the
    # measured runs pay compile stalls that swamp the scheduling signal.
    srv = AdaptiveServer(device, policy="demand", max_batch=4,
                         slo_pressure=slo_pressure, grant_quantum=1 / 16)
    return srv, heavy_p, light_p


def _slo_trace(rng, n_heavy, n_light, unit_s):
    """One Poisson trace in WALL seconds: per-tenant exponential
    inter-arrivals scaled by the measured warm-batch wall time (heavy
    load ~0.75x of its own lane alone — the light tenant and the
    exponential bursts push rounds past one batch).  Both arms replay
    the identical (at_s, tenant, sample) list."""
    shapes = {"vision-heavy": (32, 32, 8), "edge-light": (24, 24, 6)}
    arrivals = []
    t = 0.0
    for _ in range(n_heavy):
        t += float(rng.exponential(SLO_HEAVY_MEAN_IAT_UNITS * unit_s))
        arrivals.append((t, "vision-heavy"))
    t = 0.0
    for _ in range(n_light):
        t += float(rng.exponential(SLO_LIGHT_MEAN_IAT_UNITS * unit_s))
        arrivals.append((t, "edge-light"))
    arrivals.sort(key=lambda pair: pair[0])
    return [(at, name,
             rng.normal(size=shapes[name]).astype(np.float32))
            for at, name in arrivals]


def _slo_unit_seconds():
    """Warm-batch wall time (seconds) of one max-batch heavy round —
    the host-adaptive unit every deadline and inter-arrival time is
    expressed in.  Also warms the process-wide jax caches so neither
    arm pays first-trace overhead."""
    from repro.core.plan import clear_plan_cache
    clear_plan_cache()
    srv, heavy_p, light_p = _slo_deployment()
    srv.register("vision-heavy", heavy_p, (32, 32, 8))
    srv.register("edge-light", light_p, (24, 24, 6), activation="tanh",
                 ladder=(16, 8))
    rng = np.random.default_rng(7)
    times = []
    for _ in range(3):
        for _ in range(4):
            srv.submit("vision-heavy",
                       rng.normal(size=(32, 32, 8)).astype(np.float32))
        for _ in range(2):
            srv.submit("edge-light",
                       rng.normal(size=(24, 24, 6)).astype(np.float32))
        t0 = time.perf_counter()
        srv.step()
        times.append(time.perf_counter() - t0)
    return float(np.median(times[1:]))     # drop the cold round


def _slo_register(sched_or_none, srv, heavy_p, light_p, unit_s):
    """Register the two tenants — through the scheduler (with SLOs,
    light = tight deadline + priority) when given one, else on the bare
    server.  Returns the per-tenant wall deadline budget either way."""
    deadlines = {"vision-heavy": SLO_HEAVY_DEADLINE_UNITS * unit_s,
                 "edge-light": SLO_LIGHT_DEADLINE_UNITS * unit_s}
    if sched_or_none is None:
        srv.register("vision-heavy", heavy_p, (32, 32, 8))
        srv.register("edge-light", light_p, (24, 24, 6),
                     activation="tanh", ladder=(16, 8))
        return deadlines
    from repro.runtime import SLOSpec
    sched_or_none.register(
        "vision-heavy", heavy_p, (32, 32, 8),
        slo=SLOSpec(deadline_s=deadlines["vision-heavy"], priority=0))
    sched_or_none.register(
        "edge-light", light_p, (24, 24, 6), activation="tanh",
        ladder=(16, 8),
        slo=SLOSpec(deadline_s=deadlines["edge-light"], priority=1))
    return deadlines


def _slo_replay(samples, deadlines, submit, pump, pending, outcomes=None):
    """Wall-clock-driven replay shared by both arms: arrivals land on
    the real clock (sleep only when idle), and every request is judged
    from its SCHEDULED arrival instant — identical stamping for both
    arms, so neither admission policy can hide queue wait.  Returns
    per-tenant wall latencies and miss counts (a request that never
    completes — shed/rejected — counts as a miss)."""
    lat = {name: [] for name in deadlines}
    missed = {name: 0 for name in deadlines}
    arrival_s = {}
    tenant_of = {}
    i = 0
    t0 = time.monotonic()
    while i < len(samples) or pending():
        now = time.monotonic() - t0
        while i < len(samples) and samples[i][0] <= now:
            at_s, name, x = samples[i]
            rid = submit(name, x)
            arrival_s[rid] = at_s
            tenant_of[rid] = name
            i += 1
        if pending():
            comps = pump()
            done = time.monotonic() - t0
            for c in comps:
                wall = done - arrival_s[c.rid]
                lat[c.tenant].append(wall)
                if wall > deadlines[c.tenant]:
                    missed[c.tenant] += 1
        elif i < len(samples):
            time.sleep(max(0.0, min(samples[i][0] - now, 0.01)))
    if outcomes is not None:
        for rid, verdict in outcomes().items():
            if verdict in ("shed", "rejected"):
                missed[tenant_of[rid]] += 1
    total = sum(len(v) for v in lat.values())
    dropped = len(arrival_s) - total
    return lat, missed, total, dropped


def _slo_sync_arm(samples, unit_s):
    """Round-synchronous baseline: ``AdaptiveServer.step`` rounds, each
    draining every queued bucket in FIFO bucket order — arrivals during
    a round wait for the next one, and the light tenant drains behind
    the heavy backlog."""
    srv, heavy_p, light_p = _slo_deployment()
    deadlines = _slo_register(None, srv, heavy_p, light_p, unit_s)
    return _slo_replay(samples, deadlines,
                       submit=lambda name, x: srv.submit(name, x),
                       pump=srv.step, pending=srv.pending) + (None,)


def _slo_async_arm(samples, unit_s):
    """The SLO scheduler on the same trace: one launch per pump
    (continuous batching between launches, EDF + priority dispatch,
    shedding, miss-rate-weighted arbitration)."""
    from repro.runtime import SLOScheduler
    srv, heavy_p, light_p = _slo_deployment(slo_pressure=2.0)
    sched = SLOScheduler(srv)
    deadlines = _slo_register(sched, srv, heavy_p, light_p, unit_s)

    def pump():
        return sched.run(max_launches=sched.launches + 1)

    out = _slo_replay(samples, deadlines,
                      submit=lambda name, x: sched.submit(name, x),
                      pump=pump, pending=sched.pending,
                      outcomes=lambda: sched.outcomes)
    return out + (sched,)


def _slo_recovery_scenario():
    """Serve, snapshot, kill, recover, serve again — and count the cold
    plans the restart paid (the gate: ZERO)."""
    import tempfile
    from repro.core.plan import STATS, clear_plan_cache, plan_cache_stats
    from repro.runtime import (SLOScheduler, recover_server,
                               simulate_worker_death, snapshot_server)
    clear_plan_cache()
    srv, heavy_p, light_p = _slo_deployment(slo_pressure=2.0)
    sched = SLOScheduler(srv)
    _slo_register(sched, srv, heavy_p, light_p, unit_s=1.0)
    rng = np.random.default_rng(3)

    def wave(s):
        for _ in range(8):
            s.submit("vision-heavy",
                     rng.normal(size=(32, 32, 8)).astype(np.float32))
        for _ in range(4):
            s.submit("edge-light",
                     rng.normal(size=(24, 24, 6)).astype(np.float32))
        return s.run()

    # two identical waves settle the demand EWMA at the mix's
    # fixed-point ratio, so the post-crash wave re-arbitrates to the
    # SAME grants (ratio-identical targets, zero drift)
    wave(sched)
    wave(sched)
    ckpt = tempfile.mkdtemp(prefix="slo_recovery_")
    snapshot_server(srv, ckpt, 1, scheduler=sched)
    simulate_worker_death()
    misses0, hits0 = STATS.plan_misses, STATS.plan_hits
    srv2, sched2 = recover_server(ckpt)
    comps = wave(sched2)
    cold = STATS.plan_misses - misses0
    hits = STATS.plan_hits - hits0
    assert comps, "recovered scheduler served nothing"
    assert cold == 0, (
        f"plan-preserving restart paid {cold} cold re-plans "
        f"(stats: {plan_cache_stats()})")
    assert hits > 0, "recovered server never hit the imported plan cache"
    return len(comps), cold, hits, len(srv2.tenants)


def table_slo(smoke: bool = False):
    print("# Table SLO — continuous-batching SLO scheduler vs the "
          "synchronous round loop on shared wall-clock Poisson traces "
          f"(light deadline {SLO_LIGHT_DEADLINE_UNITS}x / heavy "
          f"{SLO_HEAVY_DEADLINE_UNITS}x the warm-batch wall time; "
          "p95 = worst tenant's p95 latency / its deadline), plus the "
          "plan-preserving kill/recover scenario (derived=normalized "
          "p95 + miss rate per arm + recovery_cold_plans)")
    unit_s = _slo_unit_seconds()
    # smoke replays the first full mix rather than a shortened one: the
    # strict miss-rate comparison needs the heavy backlog to persist
    # long enough that the sync loop structurally delays the light
    # tenant — a 12x4 trace is short enough for sync to get lucky
    mixes = [(16, 6)] if smoke else [(16, 6), (24, 4), (12, 12)]
    for n_heavy, n_light in mixes:
        rng = np.random.default_rng(1000 + n_heavy * 31 + n_light)
        samples = _slo_trace(rng, n_heavy, n_light, unit_s)
        n = len(samples)
        # discarded warmup replays fill the plan cache with each arm's
        # (batch-shape x slice-budget) keys — repeated until a replay
        # plans entirely from cache (wall jitter shifts batch shapes
        # between replays, so one pass can leave keys unseen).  The
        # measured replays then compare scheduling policy, not
        # cold-planning luck.
        from repro.core.plan import STATS as _PSTATS
        for arm in (_slo_sync_arm, _slo_async_arm):
            for _ in range(6):
                before = _PSTATS.plan_misses
                arm(samples, unit_s)
                if _PSTATS.plan_misses == before:
                    break
        # The SLO-centric percentile: latency only means anything
        # relative to the tenant's own deadline, so each tenant's p95
        # is normalized by its deadline budget and the system scores
        # its WORST tenant.  (Raw worst-tenant p95 would reward
        # ignoring the tight-deadline tenant — the priority scheduler
        # deliberately spends loose heavy headroom on light latency.)
        deadlines = {"vision-heavy": SLO_HEAVY_DEADLINE_UNITS * unit_s,
                     "edge-light": SLO_LIGHT_DEADLINE_UNITS * unit_s}

        def worst_norm_p95(lat):
            return max(float(np.percentile(v, 95)) / deadlines[tn]
                       for tn, v in lat.items() if v)

        # median-of-replays: one replay is a single draw of wall jitter
        # — a lucky trace can hand either arm a zero-miss run, and a
        # one-off host stall (GC, a late compile) can hand either arm a
        # catastrophic p95.  Scoring each replay separately and taking
        # the median across five draws tolerates up to two bad draws
        # per arm, so the strict comparisons measure the policy, not
        # one replay's timing.
        reps = 5

        def measure(arm):
            per_p95, per_miss, dropped, sched = [], [], 0, None
            for _ in range(reps):
                l, m, served, drop, sched = arm(samples, unit_s)
                assert served + drop == n, (served, drop, n)
                per_p95.append(worst_norm_p95(l))
                per_miss.append(sum(m.values()) / n)
                dropped += drop
            return (float(np.median(per_p95)), float(np.median(per_miss)),
                    dropped, sched)

        p95_sync, miss_sync, s_drop, _ = measure(_slo_sync_arm)
        p95_async, miss_async, a_drop, sched = measure(_slo_async_arm)
        assert s_drop == 0, s_drop
        p95_ok = p95_async < p95_sync
        miss_ok = miss_async < miss_sync
        assert p95_ok, (
            f"mix {n_heavy}x{n_light}: async worst-tenant "
            f"deadline-normalized p95 {p95_async:.3f} did not beat "
            f"sync {p95_sync:.3f}")
        assert miss_ok, (
            f"mix {n_heavy}x{n_light}: async miss rate {miss_async:.3f} "
            f"did not beat sync {miss_sync:.3f}")
        st = sched.stats()
        # the headline value is the async arm's worst-tenant p95 as a
        # FRACTION of that tenant's deadline (< 1.0 = inside SLO)
        emit(f"table_slo.mix_{n_heavy}x{n_light}", p95_async,
             f"p95_norm_sync={p95_sync:.3f}"
             f";p95_norm_async={p95_async:.3f}"
             f";miss_sync={miss_sync:.3f};miss_async={miss_async:.3f}"
             f";async_beats_sync_p95={int(p95_ok)}"
             f";async_beats_sync_miss={int(miss_ok)}"
             f";sheds={st['sheds']};preemptions={st['preemptions']}"
             f";launches={st['launches']}")
    served, cold, hits, tenants = _slo_recovery_scenario()
    emit("table_slo.recovery", 0.0,
         f"recovery_cold_plans={cold};post_restore_hits={hits}"
         f";served_after_recover={served};tenants={tenants}"
         f";recovered_ok=1")


# ---------------------------------------------------------------------------
# Table X — chaos: fault injection + degraded-mesh survival.  Three
# asserted arms over the same deterministic Poisson traffic (see
# benchmarks/_chaos_child.py for the workload):
# (a) TRANSPARENCY: a serving trace with the injector armed on a
#     never-firing schedule must be bit-identical (outputs, completion
#     times, modeled percentiles) to the disarmed trace — injection
#     must cost nothing when it does nothing;
# (b) SURVIVAL: the guarded deployment (output screening + retry_f32,
#     bounded deadline-aware retry, spare plans pre-warmed) must hold
#     availability >= 99% through one fault of every scheduled kind —
#     NaN batch, corrupted collective, kernel exception, latency
#     spike, device loss — while degrading 2 -> 1 devices with ZERO
#     cold re-plans, every plan still f32 (the degree ladder descends
#     BEFORE the precision ladder), and modeled p95 inflation bounded;
# (c) BASELINE: the identical schedule against an unguarded server
#     must collapse (poisoned answers served, batches lost, every
#     post-loss batch dead on the corpse) — the failure the survival
#     machinery exists to prevent.
# ``budget_shrink`` is deliberately absent from the chaos schedule: a
# shrunk budget re-keys every plan, so it cannot coexist with the
# zero-cold-replan assertion (its seam is covered by tests/test_faults
# and the on_budget_shrink unit path).
# Runs in a subprocess under XLA_FLAGS=--xla_force_host_platform_
# device_count=2 (JAX fixes its device count at import).
# ---------------------------------------------------------------------------
def table_chaos(smoke: bool = False):
    import os
    import subprocess
    import sys
    print("# Table X — fault injection + degraded-mesh survival: "
          "guarded serving must hold >=99% availability through "
          "nan/collective/kernel/latency/device-loss faults with zero "
          "cold re-plans (spares pre-warmed) vs an unguarded baseline "
          "that collapses; armed-but-idle injection bit-transparent")
    child = Path(__file__).resolve().parent / "_chaos_child.py"
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    # the parent holds the accelerator: the forced-host child runs on CPU
    env["JAX_PLATFORMS"] = "cpu"
    soak = 2 if smoke else max(REPEAT, 3)
    proc = subprocess.run(
        [sys.executable, str(child), str(soak)], env=env,
        capture_output=True, text=True, timeout=900)
    if proc.returncode != 0:
        raise RuntimeError(f"chaos child failed:\n{proc.stderr[-4000:]}")
    rec = json.loads(proc.stdout.splitlines()[-1])
    assert rec["devices"] == 2, \
        f"forced host mesh did not take: {rec['devices']} device(s)"
    # (a) armed-but-never-firing == disarmed, bit for bit
    assert rec["transparent"], "idle injection perturbed the serving trace"
    ch, base = rec["chaos"], rec["baseline"]
    # (b) the guarded arm survives every fault
    assert ch["availability"] >= 0.99, \
        f"guarded availability collapsed: {ch}"
    expected = {"nan_output", "collective_corrupt", "kernel_exception",
                "latency_spike", "device_loss"}
    assert set(ch["faults_fired"]) == expected, \
        f"schedule did not fire every kind: {ch['faults_fired']}"
    assert ch["cold_plans"] == 0, \
        f"degradation planned cold despite pre-warmed spares: {ch}"
    assert ch["devices_after"] == 1 and ch["degradations"] >= 1, \
        f"device loss did not degrade the mesh: {ch}"
    assert set(ch["shard_degree_mix"]) == {"1", "2"}, \
        f"serving never walked the degree ladder 2 -> 1: {ch}"
    assert set(ch["precision_mix"]) == {"32"}, \
        f"degradation moved precision, not (just) degree: {ch}"
    inflation = (ch["p95_cycles_chaos"] / ch["p95_cycles_healthy"]
                 if ch["p95_cycles_healthy"] else float("inf"))
    assert inflation < 5.0, \
        f"modeled p95 inflated {inflation:.2f}x under faults: {ch}"
    assert ch["deadline_miss_rate"] == 0.0, \
        f"generous deadlines still missed: {ch}"
    emit("table_chaos.survives", 0.0,
         f"availability={ch['availability']:.4f};available_ge_target=1"
         f";degraded_cold_plans={ch['cold_plans']}"
         f";spares_prewarmed={ch['spares_prewarmed']}"
         f";faults_fired={len(ch['faults_fired'])}"
         f";guard_retries={ch['guard_retries']}"
         f";devices=2to{ch['devices_after']}"
         f";p95_inflation={inflation:.2f};transparent=1;platform=cpu")
    # (c) the unguarded baseline loses what the guards save
    assert base["availability"] < 0.99, \
        f"unguarded baseline did not degrade: {base}"
    assert base["served_ok"] < ch["served_ok"], \
        f"guards did not out-serve the baseline: {base} vs {ch}"
    emit("table_chaos.baseline_dies", 0.0,
         f"availability={base['availability']:.4f};baseline_fails=1"
         f";lost_batches={base['lost_batches']}"
         f";served_ok={base['served_ok']}of{base['submitted']}"
         f";platform=cpu")


BENCHES = {
    "table1": table1_ip_characteristics,
    "table2": table2_resource_utilization,
    "table3": table3_comparison,
    "table_precision": table_precision,
    "table_fusion": table_fusion,
    "table_calibration": table_calibration,
    "table_serving": table_serving,
    "table_mesh": table_mesh,
    "table_obs": table_obs,
    "table_slo": table_slo,
    "table_chaos": table_chaos,
    "kernels": bench_kernels,
    "quantize": bench_quantize,
    "train_step": bench_train_step,
    "roofline": bench_roofline,
}


def main(argv=None) -> None:
    import argparse
    import inspect
    ap = argparse.ArgumentParser(description="paper-table + system benches")
    ap.add_argument("--only", default="",
                    help=f"comma list of benches to run (default all); "
                         f"have: {','.join(BENCHES)}")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced workloads for CI (benches that "
                         "support it, e.g. table_serving's single mix)")
    ap.add_argument("--repeat", type=int, default=3, metavar="N",
                    help="wall-clock runs per measurement after one "
                         "warmup; timed rows report the median (default 3)")
    ap.add_argument("--json", default="", metavar="PATH",
                    help="also write machine-readable rows "
                         "[{name, us_per_call, derived}] to PATH")
    args = ap.parse_args(argv)
    global REPEAT
    REPEAT = max(1, args.repeat)
    selected = (args.only.split(",") if args.only else list(BENCHES))
    unknown = [s for s in selected if s not in BENCHES]
    if unknown:
        raise SystemExit(f"unknown benches {unknown}; have {list(BENCHES)}")
    repo_root = Path(__file__).resolve().parent.parent
    from repro.launch.cache import use_compile_cache
    print(f"# compile cache: {use_compile_cache()}")
    print("name,us_per_call,derived")
    for name in selected:
        fn = BENCHES[name]
        kwargs = ({"smoke": True} if args.smoke
                  and "smoke" in inspect.signature(fn).parameters else {})
        start = len(ROWS)
        fn(**kwargs)
        # Per-table perf trajectory: full runs persist their rows next
        # to the repo (BENCH_<table>.json) so successive PRs can diff;
        # --smoke runs are reduced workloads and must not overwrite the
        # trajectory.
        if not args.smoke:
            table_rows = [{"name": n, "us_per_call": us, "derived": d}
                          for n, us, d in ROWS[start:]]
            (repo_root / f"BENCH_{name}.json").write_text(
                json.dumps(table_rows, indent=2))
    print(f"# total rows: {len(ROWS)}")
    if args.json:
        rows = [{"name": n, "us_per_call": us, "derived": d}
                for n, us, d in ROWS]
        Path(args.json).write_text(json.dumps(rows, indent=2))
        print(f"# wrote {len(rows)} rows to {args.json}")


if __name__ == "__main__":
    main()
