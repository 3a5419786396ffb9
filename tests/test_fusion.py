"""Fused CNN-block kernels + fusion-aware planning.

Numerics: the fused members share the standalone kernels' inner-loop
bodies, so float32 fused output is BITWISE equal to the three-launch
chain; lowered rungs stay within the deployment error bound (5e-2)
against the composite f32 oracle.  Planner: fusable conv->pool->act
triples substitute a single fused site when the combined footprint fits
and wins, fall back per group otherwise, and flow through replan —
whose strict= escape hatch verifies the fast path against a cold plan.
"""
import dataclasses
import importlib.util
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.ip import SiteSpec
from repro.core.library import CNN_FUSED, _fused_ref
from repro.core.plan import (clear_plan_cache, plan_network, planner_stats,
                             replan)
from repro.core.resources import ResourceBudget
from repro.kernels.activation.ops import activation
from repro.kernels.conv2d.ops import conv2d
from repro.kernels.fused.cnn_block import fused_cnn_mxu, fused_cnn_vpu
from repro.kernels.pool2d.ops import pool2d
from repro.models.blocks import apply_cnn_block, cnn_block_site_specs


def _unfused_chain(x, w, conv_ip, *, window, stride, mode, kind):
    y = conv2d(x, w, ip=conv_ip)
    y = pool2d(y, window=window, stride=stride, mode=mode, ip="pool_vpu")
    return activation(y, kind=kind, ip="act_vpu")


def _block_specs(shape=(2, 16, 16, 4), cout=16, ladder=(), site="blk",
                 dtype="float32", **kw):
    cin = shape[-1]
    specs, _ = cnn_block_site_specs(shape, (3, 3, cin, cout), x_dtype=dtype,
                                    site=site, ladder=ladder, **kw)
    return specs


# --------------------------------------------------------------------------
# Numerics: fused vs the three-launch path
# --------------------------------------------------------------------------
@pytest.mark.parametrize("shape,cout", [((2, 12, 12, 4), 8),
                                        ((1, 16, 16, 3), 16),
                                        ((2, 9, 11, 2), 5)])
@pytest.mark.parametrize("stride", [None, (1, 1)])
@pytest.mark.parametrize("mode,kind", [("max", "relu"), ("avg", "tanh")])
def test_fused_f32_bitwise_equals_three_launch_path(rng, shape, cout,
                                                    stride, mode, kind):
    x = jnp.asarray(rng.normal(size=shape).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(3, 3, shape[-1], cout))
                    .astype(np.float32))
    for fused, conv_ip in ((fused_cnn_vpu, "ip1_vpu"),
                           (fused_cnn_mxu, "ip2_mxu")):
        want = _unfused_chain(x, w, conv_ip, window=(2, 2), stride=stride,
                              mode=mode, kind=kind)
        got = fused(x, w, pool_window=(2, 2), pool_stride=stride,
                    pool_mode=mode, act_kind=kind)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_fused_native_int8_bitwise_equals_three_launch_path(rng):
    x = jnp.asarray(rng.integers(-20, 20, (2, 12, 12, 4)).astype(np.int8))
    w = jnp.asarray(rng.integers(-8, 8, (3, 3, 4, 8)).astype(np.int8))
    for mode in ("max", "avg"):    # int avg must keep the floor division
        want = _unfused_chain(x, w, "ip1_vpu", window=(2, 2), stride=None,
                              mode=mode, kind="relu")
        got = fused_cnn_vpu(x, w, pool_mode=mode, act_kind="relu")
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("bits", [8, 16])
@pytest.mark.parametrize("ip", ["fused_vpu", "fused_mxu"])
def test_quantized_fused_within_bound_of_oracle(rng, bits, ip):
    from repro.quant.ops import quantized_fused_cnn_block
    x = jnp.asarray(rng.normal(size=(2, 12, 12, 4)).astype(np.float32))
    w = jnp.asarray(rng.normal(0, (3 * 3 * 4) ** -0.5, size=(3, 3, 4, 8))
                    .astype(np.float32))
    ref = _fused_ref(x, w, window=(2, 2), mode="max", kind="relu")
    got = quantized_fused_cnn_block(x, w, pool_mode="max",
                                    activation="relu", bits=bits, ip=ip)
    rel = float(jnp.linalg.norm(got - ref) / jnp.linalg.norm(ref))
    assert rel <= 5e-2, rel


def test_fused_block_execution_matches_unfused_plan(rng):
    from repro.models.blocks import init_cnn_block
    blk = init_cnn_block(jax.random.PRNGKey(0), cin=4, cout=16, k=3)
    x = jnp.asarray(rng.normal(size=(2, 16, 16, 4)).astype(np.float32))
    y0 = apply_cnn_block(blk, x, activation="relu")
    plan = {}
    y1 = apply_cnn_block(blk, x, activation="relu", fuse=True, plan=plan)
    np.testing.assert_array_equal(np.asarray(y0), np.asarray(y1))
    assert list(plan) == ["cnn_block.fused"]   # ONE launch recorded


def test_fused_frontend_matches_unfused(rng):
    from repro.models.frontends import apply_cnn_frontend, init_cnn_frontend
    p = init_cnn_frontend(jax.random.PRNGKey(1), channels=(3, 8, 16),
                          d_model=32)
    imgs = jnp.asarray(rng.normal(size=(2, 16, 16, 3)).astype(np.float32))
    z0 = apply_cnn_frontend(p, imgs)
    z1 = apply_cnn_frontend(p, imgs, fuse=True)
    np.testing.assert_array_equal(np.asarray(z0), np.asarray(z1))


def test_mismatched_fused_network_rejected(rng):
    from repro.models.blocks import init_cnn_block
    blk = init_cnn_block(jax.random.PRNGKey(0), cin=3, cout=16, k=3)
    images = jnp.asarray(rng.normal(size=(2, 16, 16, 3)).astype(np.float32))
    specs, _ = cnn_block_site_specs(images.shape, blk["w"].shape,
                                    x_dtype=images.dtype, activation="relu")
    network = plan_network(specs, fuse=True)
    assert "cnn_block.fused" in network
    with pytest.raises(ValueError, match="plan/site mismatch"):
        apply_cnn_block(blk, images, activation="tanh", network=network)


# --------------------------------------------------------------------------
# Fusion-aware planning
# --------------------------------------------------------------------------
def test_fused_plan_collapses_sites_and_cycles():
    specs = []
    shape = (2, 32, 32, 8)
    for li, (cin, cout) in enumerate([(8, 16), (16, 32)]):
        layer, out = cnn_block_site_specs(shape, (3, 3, cin, cout),
                                          x_dtype="float32",
                                          site=f"fuse{li}", ladder=(16, 8))
        specs += layer
        shape = out.shape
    for budget in (ResourceBudget(), ResourceBudget(mxu_available=False),
                   ResourceBudget(vmem_bytes=4000 * 1024)):
        unfused = plan_network(specs, budget, fuse=False)
        fused = plan_network(specs, budget, fuse=True)
        assert len(fused) == 2 and len(unfused) == 6
        assert fused.total_launches == 2           # 3 -> 1 per block
        assert unfused.total_launches == 6
        assert fused.total_cycles < unfused.total_cycles
        for s in fused.sites:
            assert s.spec.family == "cnn_fused"
            assert s.footprint.hbm_bytes < sum(
                u.footprint.hbm_bytes for u in unfused.sites
                if u.spec.name.startswith(s.spec.name.split(".")[0]))


def test_fusion_is_default_with_explicit_opt_out():
    # Fusion is on by default (it is the honest est-cycles winner);
    # fuse=False remains the explicit escape hatch for per-op plans.
    specs = _block_specs(site="nofuse")
    plan = plan_network(specs, ResourceBudget())
    assert [s.spec.family for s in plan.sites] == ["cnn_fused"]
    unfused = plan_network(specs, ResourceBudget(), fuse=False)
    assert len(unfused) == 3
    assert all(s.spec.family != "cnn_fused" for s in unfused.sites)


def test_dual_conv_is_not_fused():
    conv = SiteSpec.make("d.conv", "conv2d",
                         ((2, 16, 16, 4), (3, 3, 4, 8)), "int8", dual=True)
    pool = SiteSpec.make("d.pool", "pool2d", ((2, 14, 14, 8),), "int32",
                         window=(2, 2), stride=None, mode="max")
    act = SiteSpec.make("d.act", "activation", ((2, 7, 7, 8),), "int32",
                        kind="relu")
    assert CNN_FUSED.fuse_sites((conv, pool, act)) is None


def test_nonchaining_shapes_are_not_fused():
    conv = SiteSpec.make("n.conv", "conv2d",
                         ((2, 16, 16, 4), (3, 3, 4, 8)), "float32",
                         dual=False)
    pool = SiteSpec.make("n.pool", "pool2d", ((2, 10, 10, 8),), "float32",
                         window=(2, 2), stride=None, mode="max")
    act = SiteSpec.make("n.act", "activation", ((2, 5, 5, 8),), "float32",
                        kind="relu")
    plan = plan_network((conv, pool, act), ResourceBudget(), fuse=True)
    assert all(s.spec.family != "cnn_fused" for s in plan.sites)


def test_fused_partition_failure_falls_back_per_group():
    """When a fused footprint is individually feasible but the fused
    groups jointly overflow the envelope, the planner unfuses group by
    group instead of failing — the unfused triple is the floor."""
    specs = _block_specs((2, 16, 16, 4), 16, site="fb0") + \
        _block_specs((2, 16, 16, 4), 16, site="fb1")
    budget = ResourceBudget(vmem_bytes=2200 * 1024)
    members = [CNN_FUSED.members[n] for n in sorted(CNN_FUSED.members)]
    originals = [m.footprint_fn for m in members]

    # each inflated fused group needs ~51% of the envelope: feasible at
    # full budget (and alongside one unfused triple at ~46%), but two
    # fused groups cannot share it
    def inflate(fn):
        def wrapped(*a, **kw):
            fp = fn(*a, **kw)
            return dataclasses.replace(fp, vmem_bytes=1122 * 1024)
        return wrapped

    try:
        for m, fn in zip(members, originals):
            object.__setattr__(m, "footprint_fn", inflate(fn))
        clear_plan_cache()
        before = planner_stats().fused_fallbacks
        plan = plan_network(specs, budget, fuse=True)
        # one group kept fused (it fits alone), the other unfused
        fams = [s.spec.family for s in plan.sites]
        assert fams.count("cnn_fused") == 1
        assert len(plan) == 4                  # 1 fused + 3 unfused
        assert planner_stats().fused_fallbacks > before
    finally:
        for m, fn in zip(members, originals):
            object.__setattr__(m, "footprint_fn", fn)
        clear_plan_cache()


def test_fused_dma_traffic_strictly_smaller():
    """The counted DMA saving that drives the est-cycles win: the fused
    footprint's HBM column drops the intermediate conv and pool tensors
    entirely."""
    specs = _block_specs((2, 16, 16, 4), 16, site="resc")
    unfused = plan_network(specs, ResourceBudget(), fuse=False)
    fused = plan_network(specs, ResourceBudget(), fuse=True)
    total_unfused_hbm = sum(s.footprint.hbm_bytes for s in unfused.sites)
    assert fused.site("resc.fused").footprint.hbm_bytes < total_unfused_hbm
    assert fused.total_cycles < unfused.total_cycles


# --------------------------------------------------------------------------
# replan: fusion flows through the fast path; strict= verifies it
# --------------------------------------------------------------------------
def test_replan_fast_path_serves_fused_graphs():
    specs = tuple(_block_specs((2, 32, 32, 8), 16, site="rp",
                               ladder=(16, 8)))
    clear_plan_cache()
    plan_network(specs, ResourceBudget(), fuse=True)
    stats = planner_stats()
    fast0 = stats.replan_fast
    moved = replan(specs, ResourceBudget(vmem_bytes=2 * 2**20), fuse=True)
    assert stats.replan_fast == fast0 + 1
    assert any(s.spec.family == "cnn_fused" for s in moved.sites)


def test_replan_cold_counter_counts_unknown_graphs():
    specs = tuple(_block_specs((1, 12, 12, 3), 8, site="cold"))
    clear_plan_cache()
    stats = planner_stats()
    cold0 = stats.replan_cold
    replan(specs, ResourceBudget())
    assert stats.replan_cold == cold0 + 1


@pytest.mark.parametrize("fuse", [False, True])
def test_replan_strict_matches_cold_plan(fuse):
    """The PR 4 caveat, closed: strict=True guarantees the replan result
    carries the same assignment a cold plan would choose."""
    from repro.core.plan import _assignment, _plan_uncached
    specs = tuple(_block_specs((2, 32, 32, 8), 32, site="strict",
                               ladder=(16, 8)))
    clear_plan_cache()
    plan_network(specs, ResourceBudget(), fuse=fuse)
    for vmem in (4 * 2**20, 600 * 1024, 350 * 1024):
        budget = ResourceBudget(vmem_bytes=vmem)
        try:
            got = replan(specs, budget, fuse=fuse, strict=True)
        except ValueError:
            continue
        cold = _plan_uncached(specs, budget, fuse=fuse)
        assert _assignment(got) == _assignment(cold)


def test_fused_network_with_unfusable_call_raises_value_error(rng):
    """A fused plan paired with a call whose geometry cannot fuse must
    fail with the explanatory mismatch error, not a KeyError."""
    from repro.models.blocks import init_cnn_block
    blk = init_cnn_block(jax.random.PRNGKey(0), cin=3, cout=16, k=3)
    images = jnp.asarray(rng.normal(size=(2, 16, 16, 3)).astype(np.float32))
    specs, _ = cnn_block_site_specs(images.shape, blk["w"].shape,
                                    x_dtype=images.dtype, activation="relu")
    network = plan_network(specs, fuse=True)
    with pytest.raises(ValueError, match="plan/site mismatch"):
        apply_cnn_block(blk, images, activation="relu", network=network,
                        pool_window=(3, 3))


def test_replan_strict_ignores_cached_heuristic_after_share_eviction():
    """strict=True must not trust a plan a prior non-strict replan
    cached, even when the share/fuse caches were since evicted."""
    from repro.core import plan as plan_mod
    from repro.core.plan import _assignment, _plan_uncached
    specs = tuple(_block_specs((2, 32, 32, 8), 16, site="evict",
                               ladder=(16, 8)))
    clear_plan_cache()
    plan_network(specs, ResourceBudget(), fuse=True)
    budget = ResourceBudget(vmem_bytes=2 * 2**20)
    replan(specs, budget, fuse=True)          # heuristic plan now cached
    plan_mod._SHARE_CACHE.clear()
    plan_mod._FUSE_CACHE.clear()
    got = replan(specs, budget, fuse=True, strict=True)
    assert _assignment(got) == _assignment(
        _plan_uncached(specs, budget, fuse=True))


# --------------------------------------------------------------------------
# Serving + autotune integration
# --------------------------------------------------------------------------
def test_serving_fused_lowers_latency_and_matches_numerics(rng):
    from repro.models.frontends import init_cnn_frontend
    from repro.runtime import AdaptiveServer
    params = init_cnn_frontend(jax.random.PRNGKey(0), channels=(8, 16),
                               d_model=32)
    x = rng.normal(size=(32, 32, 8)).astype(np.float32)
    results = {}
    for fuse in (False, True):
        clear_plan_cache()
        srv = AdaptiveServer(ResourceBudget(), policy="static",
                             max_batch=2, fuse=fuse)
        srv.register("t", params, (32, 32, 8))
        srv.submit("t", x)
        (c,) = srv.drain()
        results[fuse] = c
    np.testing.assert_array_equal(np.asarray(results[False].result),
                                  np.asarray(results[True].result))
    # latency is est-cycles of the executed plan: the fused plan's saved
    # HBM round-trips make the serving hot path strictly cheaper
    assert results[True].latency < results[False].latency


def test_autotune_covers_fused_sites(rng):
    from repro.core.autotune import plan_tile_overrides
    from repro.models.blocks import init_cnn_block
    specs = _block_specs((2, 16, 16, 4), 16, site="tune")
    plan = plan_network(specs, ResourceBudget(), fuse=True)
    overrides = plan_tile_overrides(plan)
    assert "tune.fused" in overrides
    assert "block_cout" in overrides["tune.fused"]
    blk = init_cnn_block(jax.random.PRNGKey(0), cin=4, cout=16, k=3)
    x = jnp.asarray(rng.normal(size=(2, 16, 16, 4)).astype(np.float32))
    y0 = apply_cnn_block(blk, x, activation="relu", site="tune")
    y1 = apply_cnn_block(blk, x, activation="relu", site="tune",
                         network=plan, tile_overrides=overrides)
    np.testing.assert_array_equal(np.asarray(y0), np.asarray(y1))


# --------------------------------------------------------------------------
# Bench acceptance (benchmarks/run.py::table_fusion)
# --------------------------------------------------------------------------
def _load_bench():
    path = (pathlib.Path(__file__).resolve().parent.parent
            / "benchmarks" / "run.py")
    spec = importlib.util.spec_from_file_location("bench_run_fusion", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_table_fusion_reports_modeled_and_measured_separately():
    bench = _load_bench()
    bench.table_fusion()
    rows = [d for n, _, d in bench.ROWS if n.startswith("table_fusion.")]
    assert rows
    both = [d for d in rows if "unfused=x" not in d and "fused=x" not in d]
    # The analytical model prices fused strictly cheaper on >= 2 budgets
    # (the counted DMA-byte saving) — a claim about the MODEL only.
    assert sum("modeled_wins=1" in d for d in both) >= 2, both
    # The measured verdict must be reported as its OWN flag on every
    # row (never asserted to equal the modeled one: the two disagreeing
    # is real data — it is why the calibration layer exists).
    for d in both:
        assert "measured_wins=" in d, d
        assert "never_worse" not in d and "fused_wins" not in d, d
    # launch count 3 -> 1 per block, errors within the deployment bound
    for d in both:
        assert "launches_unfused=9" in d and "launches_fused=3" in d, d
        assert "err_ok=1" in d, d
    # every executed fused plan stays in bound, the int8 rung (the
    # in-register rescale) among them
    ran = [d for d in rows if "us_fused=" in d]
    assert all("err_ok=1" in d for d in ran), ran
    bits = [d.split(";bits=")[1].split(";")[0].split("|") for d in ran]
    assert any(b.endswith(":8") for row in bits for b in row), ran
