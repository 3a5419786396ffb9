"""Network planner: one selection engine, partitioned budgets, cached &
serializable plans."""
import importlib.util
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.ip import SiteSpec
from repro.core.plan import (NetworkPlan, clear_plan_cache,
                             fixed_network_cost, plan_network, planner_stats,
                             select_ip)
from repro.core.resources import ResourceBudget
from repro.core.selector import (select_activation_ip, select_attention_ip,
                                 select_conv_ip, select_matmul_ip,
                                 select_pool_ip)

CONV_SHAPE = ((2, 32, 32, 3), (3, 3, 3, 16))

BUDGET_MATRIX = [
    ResourceBudget(),
    ResourceBudget(mxu_available=False),
    ResourceBudget(vpu_ops_budget=100_000),
    ResourceBudget(vmem_bytes=2 * 2**20),
    ResourceBudget(precision_bits=8, prefer_parallel_streams=True),
    ResourceBudget(precision_bits=8, mxu_passes_budget=1),
]


def _cnn_specs(site_prefix="net", n=2, hw=32, layers=((8, 16), (16, 32))):
    specs = []
    h = w = hw
    for li, (cin, cout) in enumerate(layers):
        conv_out = (n, h - 2, w - 2, cout)
        pool_out = (n, conv_out[1] // 2, conv_out[2] // 2, cout)
        specs += [
            SiteSpec.make(f"{site_prefix}{li}.conv", "conv2d",
                          ((n, h, w, cin), (3, 3, cin, cout)), "int8",
                          dual=False),
            SiteSpec.make(f"{site_prefix}{li}.pool", "pool2d", (conv_out,),
                          "int32", window=(2, 2), mode="max"),
            SiteSpec.make(f"{site_prefix}{li}.act", "activation", (pool_out,),
                          "int32", kind="relu"),
        ]
        h, w = pool_out[1], pool_out[2]
    return specs


# --------------------------------------------------------------------------
# Shim equivalence: the five historical entry points vs the generic engine
# --------------------------------------------------------------------------
@pytest.mark.parametrize("budget", BUDGET_MATRIX)
def test_select_conv_shim_equals_generic(budget):
    for dual in (False, True):
        spec = SiteSpec.make("s", "conv2d", CONV_SHAPE, jnp.int8, dual=dual)
        try:
            want = select_conv_ip(*CONV_SHAPE, dual=dual, dtype=jnp.int8,
                                  budget=budget, with_footprint=True)
        except ValueError:
            with pytest.raises(ValueError, match="no feasible IP"):
                select_ip("conv2d", spec, budget=budget)
            continue
        got = select_ip("conv2d", spec, budget=budget, with_footprint=True)
        assert got[0] is want[0]
        assert got[1] == want[1]


@pytest.mark.parametrize("budget", BUDGET_MATRIX)
def test_other_family_shims_equal_generic(budget):
    cases = [
        ("pool2d",
         lambda: select_pool_ip((2, 16, 16, 8), window=(2, 2), mode="avg",
                                dtype=jnp.int32, budget=budget),
         SiteSpec.make("s", "pool2d", ((2, 16, 16, 8),), jnp.int32,
                       window=(2, 2), stride=None, mode="avg")),
        ("activation",
         lambda: select_activation_ip((2, 8, 8, 16), kind="tanh",
                                      dtype=jnp.float32, budget=budget),
         SiteSpec.make("s", "activation", ((2, 8, 8, 16),), jnp.float32,
                       kind="tanh")),
        ("matmul",
         lambda: select_matmul_ip((256, 256), (256, 256), dual=False,
                                  dtype=jnp.bfloat16, budget=budget),
         SiteSpec.make("s", "matmul", ((256, 256), (256, 256)),
                       jnp.bfloat16, dual=False)),
        ("attention",
         lambda: select_attention_ip((2, 8, 128, 64), (2, 2, 128, 64),
                                     budget=budget),
         SiteSpec.make("s", "attention", ((2, 8, 128, 64), (2, 2, 128, 64)),
                       jnp.bfloat16)),
    ]
    for family, shim, spec in cases:
        try:
            want = shim()
        except ValueError:
            with pytest.raises(ValueError, match="no feasible IP"):
                select_ip(family, spec, budget=budget)
            continue
        assert select_ip(family, spec, budget=budget) is want


# --------------------------------------------------------------------------
# Budget partitioning
# --------------------------------------------------------------------------
def test_partitioned_slices_fit_and_sum_to_one():
    budget = ResourceBudget(vpu_ops_budget=2_000_000)
    plan = plan_network(_cnn_specs(), budget)
    assert abs(sum(s.fraction for s in plan.sites) - 1.0) < 1e-6
    for s in plan.sites:
        assert s.footprint.fits(budget.scaled(s.fraction)), s.spec.name


def test_partition_repair_rescues_starved_site():
    """A huge conv dwarfs a small one: proportional-to-cost alone gives
    the small site a VMEM slice below any member's working set, and the
    greedy repair pass must floor it back to feasibility."""
    specs = [
        SiteSpec.make("big.conv", "conv2d",
                      ((4, 32, 32, 16), (3, 3, 16, 32)), "int8", dual=False),
        SiteSpec.make("small.conv", "conv2d",
                      ((1, 16, 16, 8), (3, 3, 8, 16)), "int8", dual=False),
    ]
    # big ip2 needs ~528 KiB vmem, small ~304 KiB; big's cost share is
    # ~99%, so under a 1 MiB envelope the small site's proportional
    # slice (~10 KiB) fits nothing.
    budget = ResourceBudget(vmem_bytes=2**20)
    plan = plan_network(specs, budget)
    small = plan.site("small.conv")
    assert small.footprint.fits(budget.scaled(small.fraction))
    assert small.fraction > 0.01  # repair raised it above the cost share
    assert abs(sum(s.fraction for s in plan.sites) - 1.0) < 1e-6


def test_no_feasible_partition_raises():
    # Each site alone fits the envelope (~528 KiB need vs 1 MiB), but
    # eight of them jointly demand ~4x it.
    specs = [
        SiteSpec.make(f"c{i}.conv", "conv2d",
                      ((4, 32, 32, 16), (3, 3, 16, 32)), "int8", dual=False)
        for i in range(8)
    ]
    single = plan_network(specs[:1], ResourceBudget(vmem_bytes=2**20))
    assert len(single) == 1
    with pytest.raises(ValueError, match="no feasible network plan"):
        plan_network(specs, ResourceBudget(vmem_bytes=2**20))


def test_site_infeasible_under_full_budget_raises_family_error():
    spec = SiteSpec.make("c.conv", "conv2d", CONV_SHAPE, jnp.int16, dual=True)
    with pytest.raises(ValueError, match="no feasible IP"):
        plan_network([spec], ResourceBudget(precision_bits=16,
                                            mxu_available=False))


def test_duplicate_site_names_rejected():
    spec = SiteSpec.make("dup", "conv2d", CONV_SHAPE, jnp.int8, dual=False)
    with pytest.raises(ValueError, match="duplicate site names"):
        plan_network([spec, spec], ResourceBudget())


# --------------------------------------------------------------------------
# Plan cache
# --------------------------------------------------------------------------
def test_plan_cache_returns_identical_object_with_zero_evals():
    budget = ResourceBudget(vmem_bytes=32 * 2**20)
    first = plan_network(_cnn_specs("cache"), budget)
    evals = planner_stats().selector_evals
    second = plan_network(_cnn_specs("cache"), budget)
    assert second is first
    assert planner_stats().selector_evals == evals


def test_plan_cache_distinguishes_budgets():
    a = plan_network(_cnn_specs("cacheb"), ResourceBudget())
    b = plan_network(_cnn_specs("cacheb"), ResourceBudget(mxu_available=False))
    assert a is not b


def test_second_cnn_block_trace_performs_zero_selector_evals(rng):
    from repro.models.blocks import apply_cnn_block, init_cnn_block
    block = init_cnn_block(jax.random.PRNGKey(0), cin=3, cout=16, k=3)
    images = jnp.asarray(rng.normal(size=(2, 16, 16, 3)).astype(np.float32))
    y1 = apply_cnn_block(block, images, activation="relu")
    evals = planner_stats().selector_evals
    y2 = apply_cnn_block(block, images, activation="relu")
    assert planner_stats().selector_evals == evals
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2))


def test_mismatched_external_network_rejected(rng):
    from repro.models.blocks import (apply_cnn_block, cnn_block_site_specs,
                                     init_cnn_block)
    block = init_cnn_block(jax.random.PRNGKey(0), cin=3, cout=16, k=3)
    images = jnp.asarray(rng.normal(size=(2, 16, 16, 3)).astype(np.float32))
    specs, _ = cnn_block_site_specs(images.shape, block["w"].shape,
                                    x_dtype=images.dtype, activation="relu")
    network = plan_network(specs)
    with pytest.raises(ValueError, match="plan/site mismatch"):
        apply_cnn_block(block, images, activation="tanh", network=network)


def test_frontend_plans_whole_stack_as_one_network(rng):
    from repro.core import plan as plan_mod
    from repro.models.frontends import apply_cnn_frontend, init_cnn_frontend
    p = init_cnn_frontend(jax.random.PRNGKey(1), channels=(3, 8, 16),
                          d_model=32)
    imgs = jnp.asarray(rng.normal(size=(2, 16, 16, 3)).astype(np.float32))
    clear_plan_cache()
    misses = planner_stats().plan_misses
    out = {}
    apply_cnn_frontend(p, imgs, plan=out, fuse=False)
    # one whole-network plan covering both blocks, not one per block
    assert planner_stats().plan_misses == misses + 1
    assert len(out) == 6
    key = next(k for k in plan_mod._PLAN_CACHE
               if len(k[0]) == 6)  # 2 blocks x 3 sites in ONE graph key
    assert {s.name.split(".")[0] for s in key[0]} == {"frontend"}


# --------------------------------------------------------------------------
# Serialization
# --------------------------------------------------------------------------
def test_plan_json_round_trip():
    budget = ResourceBudget(vpu_ops_budget=2_000_000, precision_bits=8)
    plan = plan_network(_cnn_specs("json"), budget)
    restored = NetworkPlan.from_json(plan.to_json())
    assert restored == plan
    assert restored.budget == budget
    for name, (ip, fp) in plan.items():
        rip, rfp = restored[name]
        assert rip is ip          # re-linked to the live registry object
        assert rfp == fp
    assert restored.total_cycles == plan.total_cycles


def test_sitespec_round_trip_preserves_tuple_knobs():
    spec = SiteSpec.make("s.pool", "pool2d", ((2, 16, 16, 8),), "int32",
                         window=(2, 2), stride=None, mode="max")
    back = SiteSpec.from_dict(spec.to_dict())
    assert back == spec
    assert back.knob("window") == (2, 2)
    hash(back)  # knobs stay hashable after the JSON round-trip


# --------------------------------------------------------------------------
# scaled() (satellite): the ceilings must scale with the slice
# --------------------------------------------------------------------------
def test_scaled_budget_scales_pass_and_op_ceilings():
    b = ResourceBudget(mxu_passes_budget=100, vpu_ops_budget=1_000_000)
    half = b.scaled(0.5)
    assert half.mxu_passes_budget == 50
    assert half.vpu_ops_budget == 500_000
    assert half.vmem_bytes == b.vmem_bytes // 2
    none = ResourceBudget().scaled(0.25)
    assert none.mxu_passes_budget is None and none.vpu_ops_budget is None
    assert b.scaled(0.5).precision_bits == b.precision_bits


# --------------------------------------------------------------------------
# Planned vs fixed networks (benchmarks/run.py::table3 acceptance)
# --------------------------------------------------------------------------
def _load_bench():
    path = (pathlib.Path(__file__).resolve().parent.parent
            / "benchmarks" / "run.py")
    spec = importlib.util.spec_from_file_location("bench_run", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_planned_network_beats_every_fixed_baseline_somewhere():
    bench = _load_bench()
    bench.table3_comparison()
    rows = [d for n, _, d in bench.ROWS if n.startswith("table3.")]
    assert rows
    assert any("planned_best=1" in d for d in rows), rows


def test_fixed_network_cost_infeasible_is_none():
    specs = _cnn_specs("fix")
    assert fixed_network_cost(
        specs, {"conv2d": "ip2_mxu", "pool2d": "pool_im2col",
                "activation": "act_vpu"},
        ResourceBudget(mxu_available=False)) is None
    cost = fixed_network_cost(
        specs, {"conv2d": "ip1_vpu", "pool2d": "pool_vpu",
                "activation": "act_vpu"}, ResourceBudget())
    assert cost is not None and cost > 0


# --------------------------------------------------------------------------
# Cache correctness under calibration: a refreshed table must invalidate
# stale plans and stale replan shares (core/calibrate_cost.py)
# --------------------------------------------------------------------------
def _refit(table, plan):
    """Record 3 synthetic samples against a planned site and refit —
    the minimal operation that moves the table's identity."""
    site = plan.sites[0]
    for us in (10.0, 20.0, 30.0):
        table.record(site.ip.name, site.footprint, us,
                     bits=site.precision_bits,
                     native_bits=site.spec.native_bits)
    return table.fit()


def test_plan_cache_keys_on_calibration_identity():
    from repro.core.calibrate_cost import CalibrationTable
    specs = tuple(_cnn_specs("calkey"))
    budget = ResourceBudget()
    clear_plan_cache()
    table = CalibrationTable()
    stats = planner_stats()
    misses0 = stats.plan_misses
    p1 = plan_network(specs, budget, calibration=table)
    assert stats.plan_misses == misses0 + 1
    # identical table identity -> cache hit, same object
    hits0 = stats.plan_hits
    assert plan_network(specs, budget, calibration=table) is p1
    assert stats.plan_hits == hits0 + 1
    # refitting moves key(): the same call must MISS (no stale plan)
    key0 = table.key()
    _refit(table, p1)
    assert table.key() != key0
    misses1 = stats.plan_misses
    plan_network(specs, budget, calibration=table)
    assert stats.plan_misses == misses1 + 1


def test_calibrated_and_uncalibrated_plans_cached_separately():
    from repro.core import plan as plan_mod
    from repro.core.calibrate_cost import CalibrationTable
    specs = tuple(_cnn_specs("calsep"))
    budget = ResourceBudget()
    clear_plan_cache()
    plan_network(specs, budget)
    plan_network(specs, budget, calibration=CalibrationTable())
    keys = [k for k in plan_mod._PLAN_CACHE if k[0] == specs]
    assert len(keys) == 2
    # key layout: (specs, budget, fuse, mesh, calibration_key)
    assert {k[4] for k in keys} == {None,
                                    CalibrationTable().key()}


def test_replan_shares_keyed_on_calibration_identity():
    from repro.core.calibrate_cost import CalibrationTable
    from repro.core.plan import replan
    specs = tuple(_cnn_specs("calshare"))
    table = CalibrationTable()
    clear_plan_cache()
    stats = planner_stats()
    warm = replan(specs, ResourceBudget(), calibration=table)  # warms shares
    fast0 = stats.replan_fast
    replan(specs, ResourceBudget(vmem_bytes=2 * 2**20), calibration=table)
    assert stats.replan_fast == fast0 + 1
    # a REFIT table must not serve off the stale shares: same graph,
    # same budget shape, but the share lookup misses and falls cold
    _refit(table, warm)
    cold0 = stats.replan_cold
    replan(specs, ResourceBudget(vmem_bytes=3 * 2**20), calibration=table)
    assert stats.replan_cold == cold0 + 1


def test_replan_strict_agrees_with_cold_calibrated_plan():
    from repro.core import plan as plan_mod
    from repro.core.calibrate_cost import AffineFit, CalibrationTable
    from repro.core.plan import replan
    specs = tuple(_cnn_specs("calstrict"))
    budget = ResourceBudget(vmem_bytes=4 * 2**20)
    clear_plan_cache()
    # a table that actually changes decisions: the analytical conv
    # winner is priced as measured-terrible (fuse=False throughout —
    # the scenario targets the per-op conv member)
    base = plan_network(specs, ResourceBudget(), fuse=False)
    conv_winner = next(s.ip.name for s in base.sites
                       if s.spec.family == "conv2d")
    table = CalibrationTable(
        fits={conv_winner: AffineFit(0.0, 0.0, 1e6, 3)})
    got = replan(specs, budget, strict=True, fuse=False, calibration=table)
    cold = plan_mod._plan_uncached(specs, budget, fuse=False,
                                   calibration=table)
    assert plan_mod._assignment(got) == plan_mod._assignment(cold)
    assert all(s.ip.name != conv_winner for s in got.sites)
