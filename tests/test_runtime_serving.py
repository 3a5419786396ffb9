"""Serving runtime: arbiter split semantics, shape-bucketed batching
correctness, ladder descent under budget pressure, plan-cache
statistics, and the replan fast path."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import plan as plan_mod
from repro.core.ip import SiteSpec
from repro.core.plan import (clear_plan_cache, network_min_fraction,
                             plan_cache_stats, plan_network, planner_stats,
                             replan)
from repro.core.resources import ResourceBudget
from repro.models.frontends import apply_cnn_frontend, init_cnn_frontend
from repro.runtime import AdaptiveServer, BudgetArbiter, ShapeBucketQueue
from repro.runtime.batching import Request

SERVING_DEVICE = ResourceBudget(vpu_ops_budget=15_000_000)


def _frontend(key=0, channels=(6, 12), d_model=16):
    return init_cnn_frontend(jax.random.PRNGKey(key), channels=channels,
                             d_model=d_model)


# --------------------------------------------------------------------------
# Arbiter: proportional split + needs-floor interaction
# --------------------------------------------------------------------------
def test_arbiter_demand_proportional_with_floors():
    arb = BudgetArbiter(ResourceBudget(), rebalance_threshold=0.01,
                        demand_alpha=1.0)
    arb.register("a", floor=0.3)
    arb.register("b", floor=0.1)
    arb.observe("a", 100.0)
    arb.observe("b", 900.0)
    shares = arb.split()
    # surplus 0.6 follows demand: a = 0.3 + 0.6*0.1, b = 0.1 + 0.6*0.9
    assert shares["a"].fraction == pytest.approx(0.36)
    assert shares["b"].fraction == pytest.approx(0.64)
    assert sum(s.fraction for s in shares.values()) == pytest.approx(1.0)
    # every grant respects its floor no matter the skew
    assert shares["a"].fraction >= shares["a"].floor
    assert shares["b"].fraction >= shares["b"].floor


def test_arbiter_static_ignores_demand():
    arb = BudgetArbiter(ResourceBudget(), policy="static")
    arb.register("a", floor=0.3)
    arb.register("b", floor=0.0)
    arb.observe("a", 1.0)
    arb.observe("b", 1e9)
    shares = arb.split()
    assert shares["a"].fraction == pytest.approx(0.5)
    assert shares["b"].fraction == pytest.approx(0.5)


def test_arbiter_floors_exceeding_envelope_rejected():
    arb = BudgetArbiter(ResourceBudget())
    arb.register("a", floor=0.7)
    with pytest.raises(ValueError, match="jointly need"):
        arb.register("b", floor=0.5)
    # regression: a rejected registration leaves no ghost tenant behind
    assert "b" not in arb._floors
    shares = arb.split()
    assert set(shares) == {"a"}
    # and the name is re-registrable with feasible parameters
    arb.register("b", floor=0.1)
    assert set(arb.split()) == {"a", "b"}


def test_arbiter_static_rejects_floor_above_even_share():
    """Regression: static policy grants an unconditional 1/n, so a
    tenant whose floor exceeds that must be rejected at admission (the
    demand policy would happily serve the same pair)."""
    arb = BudgetArbiter(ResourceBudget(), policy="static")
    arb.register("a", floor=0.65)       # fine alone: 1/1 grant
    with pytest.raises(ValueError, match="static even split"):
        arb.register("b", floor=0.1)    # would shrink a's grant to 0.5
    assert "b" not in arb._floors
    demand = BudgetArbiter(ResourceBudget(), policy="demand")
    demand.register("a", floor=0.65)
    demand.register("b", floor=0.1)     # jointly 0.75: demand serves it


def test_arbiter_hysteresis_gates_rebalances():
    arb = BudgetArbiter(ResourceBudget(), rebalance_threshold=0.2,
                        demand_alpha=1.0)
    arb.register("a")
    arb.register("b")
    arb.observe("a", 100.0)
    arb.observe("b", 100.0)
    first = arb.split()
    assert arb.rebalances == 0          # initial grant is not a rebalance
    # small drift: inside the threshold, grants hold
    arb.observe("a", 120.0)
    arb.observe("b", 100.0)
    held = arb.split()
    assert held["a"].fraction == first["a"].fraction
    assert arb.rebalances == 0
    # large drift: grants snap to target
    arb.observe("a", 1000.0)
    arb.observe("b", 10.0)
    moved = arb.split()
    assert moved["a"].fraction > 0.8
    assert arb.rebalances == 1


def test_arbiter_late_registration_regrants():
    """Regression: a tenant registered after the first split must be
    granted on the next round even when no drift crosses the
    hysteresis threshold."""
    arb = BudgetArbiter(ResourceBudget(), rebalance_threshold=0.05,
                        demand_alpha=1.0)
    arb.register("a", floor=0.3)
    arb.observe("a", 100.0)
    arb.split()
    arb.register("b", floor=0.02)       # low floor, zero demand
    arb.observe("a", 100.0)
    shares = arb.split()                # must not KeyError
    assert shares["b"].fraction >= shares["b"].floor
    assert sum(s.fraction for s in shares.values()) == pytest.approx(1.0)
    assert arb.rebalances == 1          # topology change forced a re-grant


def test_network_min_fraction_is_feasibility_threshold():
    specs = tuple(
        SiteSpec.make(f"c{i}.conv", "conv2d",
                      ((2, 16, 16, 8), (3, 3, 8, 16)), "int8", dual=False)
        for i in range(3))
    budget = ResourceBudget(vmem_bytes=2 * 2**20)
    floor = network_min_fraction(specs, budget)
    assert 0.0 < floor <= 1.0
    plan_network(specs, budget.scaled(min(1.0, floor * 1.05)))  # feasible
    if floor > 0.02:
        with pytest.raises(ValueError):
            plan_network(specs, budget.scaled(floor * 0.5))


# --------------------------------------------------------------------------
# Shape-bucketed batching
# --------------------------------------------------------------------------
def test_bucket_queue_groups_by_tenant_and_shape():
    q = ShapeBucketQueue()
    a1 = np.zeros((4, 4, 1), np.float32)
    a2 = np.zeros((8, 8, 1), np.float32)
    for rid, (tenant, x) in enumerate([("t1", a1), ("t1", a1), ("t2", a1),
                                       ("t1", a2)]):
        q.push(Request(rid=rid, tenant=tenant, x=x, arrival=0.0))
    assert len(q) == 4
    assert q.pending("t1") == 3
    keys = q.keys()
    assert len(keys) == 3               # (t1, 4x4), (t2, 4x4), (t1, 8x8)
    batch = q.pop_batch(keys[0], max_batch=8)
    assert [r.rid for r in batch] == [0, 1]   # FIFO within the bucket
    assert q.pending("t1") == 1


def test_server_batching_matches_per_request_execution(rng):
    clear_plan_cache()
    params = _frontend()
    srv = AdaptiveServer(ResourceBudget(), max_batch=4)
    srv.register("t", params, (12, 12, 6))
    xs = [rng.normal(size=(12, 12, 6)).astype(np.float32) for _ in range(5)]
    rids = [srv.submit("t", x) for x in xs]
    completions = {c.rid: c for c in srv.drain()}
    assert len(completions) == 5
    # 5 requests at max_batch 4 -> batches of 4 and 1
    assert sorted(c.batch_size for c in completions.values()) == \
        [1, 4, 4, 4, 4]
    for rid, x in zip(rids, xs):
        want = apply_cnn_frontend(params, jnp.asarray(x)[None])[0]
        np.testing.assert_allclose(np.asarray(completions[rid].result),
                                   np.asarray(want), rtol=1e-5, atol=1e-5)


def test_server_buckets_mixed_shapes_separately(rng):
    clear_plan_cache()
    params = _frontend()
    srv = AdaptiveServer(ResourceBudget(), max_batch=4)
    srv.register("t", params, (12, 12, 6))
    small = rng.normal(size=(12, 12, 6)).astype(np.float32)
    with pytest.raises(ValueError, match="expects samples of shape"):
        srv.submit("t", rng.normal(size=(16, 16, 6)).astype(np.float32))
    rid = srv.submit("t", small)
    (done,) = srv.drain()
    assert done.rid == rid and done.batch_size == 1


def test_server_batch_submission_fans_out(rng):
    clear_plan_cache()
    srv = AdaptiveServer(ResourceBudget(), max_batch=4)
    srv.register("t", _frontend(), (12, 12, 6))
    rids = srv.submit("t", rng.normal(size=(3, 12, 12, 6)).astype(np.float32))
    assert len(rids) == 3
    done = srv.drain()
    assert {c.rid for c in done} == set(rids)
    assert all(c.batch_size == 3 for c in done)


# --------------------------------------------------------------------------
# Ladder descent under budget pressure (degrade-before-fail)
# --------------------------------------------------------------------------
def test_squeezed_tenant_descends_ladder_within_error_bound(rng):
    clear_plan_cache()
    # fuse=False: the squeeze thresholds below were sized against the
    # per-op footprints — the fused group fits the slice without lowering.
    # 16 MiB of VMEM (the compiler's default scoped limit) is what makes
    # the light tenant's slice too small for its f32 working set.
    device = ResourceBudget(vmem_bytes=16 * 2**20,
                            vpu_ops_budget=SERVING_DEVICE.vpu_ops_budget)
    srv = AdaptiveServer(device, policy="demand", max_batch=4, fuse=False)
    srv.register("heavy", _frontend(0, channels=(8, 16), d_model=32),
                 (32, 32, 8))
    srv.register("light", _frontend(1), (24, 24, 6), activation="tanh",
                 ladder=(16, 8), measure_quant=True)
    for _ in range(10):
        srv.submit("heavy", rng.normal(size=(32, 32, 8)).astype(np.float32))
    for _ in range(2):
        srv.submit("light", rng.normal(size=(24, 24, 6)).astype(np.float32))
    srv.drain()
    tel = srv.telemetry()
    light = tel["light"]
    # squeezed below its f32 footprint, the tenant serves lowered...
    assert light["granted_fraction"] < 0.15
    assert light["lowered_fraction"] > 0.0
    assert any(b < 32 for b in light["precision_mix"])
    # ...within the documented error bound
    assert 0.0 < light["max_quant_rel_err"] <= 5e-2
    # the heavy tenant was granted the bulk and stayed full-precision
    heavy = tel["heavy"]
    assert heavy["granted_fraction"] > 0.8
    assert set(heavy["precision_mix"]) == {32}


def test_static_even_split_leaves_light_tenant_at_f32(rng):
    clear_plan_cache()
    srv = AdaptiveServer(SERVING_DEVICE, policy="static", max_batch=4)
    srv.register("heavy", _frontend(0, channels=(8, 16), d_model=32),
                 (32, 32, 8))
    srv.register("light", _frontend(1), (24, 24, 6), activation="tanh",
                 ladder=(16, 8), measure_quant=True)
    for _ in range(4):
        srv.submit("heavy", rng.normal(size=(32, 32, 8)).astype(np.float32))
    srv.submit("light", rng.normal(size=(24, 24, 6)).astype(np.float32))
    srv.drain()
    light = srv.telemetry()["light"]
    assert light["granted_fraction"] == pytest.approx(0.5)
    assert set(light["precision_mix"]) == {32}


def test_infeasible_tenant_rejected_at_registration():
    clear_plan_cache()
    srv = AdaptiveServer(ResourceBudget(vmem_bytes=1024), max_batch=2)
    with pytest.raises(ValueError, match="no feasible"):
        srv.register("t", _frontend(), (12, 12, 6))


def test_registration_prices_the_max_batch_graph_too():
    """Regression: a tenant whose one-sample graph fits the device but
    whose max-batch graph does not must be rejected at admission, not
    crash at serving time with requests already dequeued."""
    clear_plan_cache()
    device = ResourceBudget(vpu_ops_budget=80_000)
    srv = AdaptiveServer(device, max_batch=4)
    with pytest.raises(ValueError, match="no feasible"):
        srv.register("t", _frontend(1), (24, 24, 6), activation="tanh")
    # the same tenant at max_batch=1 is admissible
    srv1 = AdaptiveServer(device, max_batch=1)
    srv1.register("t", _frontend(1), (24, 24, 6), activation="tanh")


# --------------------------------------------------------------------------
# Plan-cache statistics + eviction
# --------------------------------------------------------------------------
def test_plan_cache_stats_track_hits_and_misses():
    clear_plan_cache()
    spec = SiteSpec.make("s.conv", "conv2d",
                         ((2, 16, 16, 8), (3, 3, 8, 16)), "int8", dual=False)
    before = plan_cache_stats()
    plan_network([spec], ResourceBudget())
    plan_network([spec], ResourceBudget())
    after = plan_cache_stats()
    assert after["misses"] == before["misses"] + 1
    assert after["hits"] == before["hits"] + 1
    assert after["size"] >= 1
    assert after["capacity"] == plan_mod._PLAN_CACHE_MAX
    assert 0.0 <= after["hit_rate"] <= 1.0


def test_plan_cache_evicts_lru_at_capacity(monkeypatch):
    clear_plan_cache()
    monkeypatch.setattr(plan_mod, "_PLAN_CACHE_MAX", 2)
    def spec(i):
        return SiteSpec.make(f"s{i}.conv", "conv2d",
                             ((1, 8 + i, 8 + i, 4), (3, 3, 4, 8)),
                             "int8", dual=False)
    ev0 = planner_stats().plan_evictions
    plan_network([spec(0)], ResourceBudget())
    plan_network([spec(1)], ResourceBudget())
    plan_network([spec(0)], ResourceBudget())     # refresh 0 -> 1 is LRU
    plan_network([spec(2)], ResourceBudget())     # evicts 1
    assert planner_stats().plan_evictions == ev0 + 1
    assert len(plan_mod._PLAN_CACHE) == 2
    misses = planner_stats().plan_misses
    plan_network([spec(0)], ResourceBudget())     # still cached
    assert planner_stats().plan_misses == misses
    plan_network([spec(1)], ResourceBudget())     # was evicted: a miss
    assert planner_stats().plan_misses == misses + 1


# --------------------------------------------------------------------------
# replan(): the live re-planning fast path
# --------------------------------------------------------------------------
def _replan_specs():
    return tuple(
        SiteSpec.make(f"r{i}.conv", "conv2d",
                      ((2, 24, 24, 8), (3, 3, 8, 16)), "float32",
                      ladder=(16, 8), dual=False)
        for i in range(2))


def test_replan_skips_baseline_on_known_graph():
    clear_plan_cache()
    specs = _replan_specs()
    plan_network(specs, ResourceBudget())          # seeds the cost shares
    evals_cold = planner_stats().selector_evals
    fast = planner_stats().replan_fast
    new_budget = ResourceBudget(vmem_bytes=16 * 2**20)
    plan = replan(specs, new_budget)
    assert planner_stats().replan_fast == fast + 1
    assert plan.budget == new_budget
    assert abs(sum(s.fraction for s in plan.sites) - 1.0) < 1e-6
    for s in plan.sites:
        assert s.footprint.fits(new_budget.scaled(s.fraction)), s.spec.name
    # an identical replan is a pure cache hit
    evals = planner_stats().selector_evals
    assert replan(specs, new_budget) is plan
    assert planner_stats().selector_evals == evals
    assert evals > evals_cold          # the fast path did *some* work...
    # ...but strictly less than a cold plan of the same graph
    clear_plan_cache()
    e0 = planner_stats().selector_evals
    plan_network(specs, new_budget)
    cold_evals = planner_stats().selector_evals - e0
    assert evals - evals_cold < cold_evals


def test_replan_cold_graph_falls_through_to_plan_network():
    clear_plan_cache()
    specs = _replan_specs()
    fast = planner_stats().replan_fast
    plan = replan(specs, ResourceBudget())
    assert planner_stats().replan_fast == fast     # no fast path taken
    assert plan is plan_network(specs, ResourceBudget())


def test_replan_surfaces_canonical_infeasibility():
    clear_plan_cache()
    specs = _replan_specs()
    plan_network(specs, ResourceBudget())
    with pytest.raises(ValueError, match="no feasible"):
        replan(specs, ResourceBudget(vmem_bytes=4 * 1024))


def test_server_counts_replans_on_grant_moves(rng):
    clear_plan_cache()
    srv = AdaptiveServer(SERVING_DEVICE, policy="demand", max_batch=2,
                         rebalance_threshold=0.05)
    srv.register("a", _frontend(0), (12, 12, 6))
    srv.register("b", _frontend(1), (12, 12, 6))
    x = rng.normal(size=(12, 12, 6)).astype(np.float32)
    # wave 1: balanced -> ~even grants
    srv.submit("a", x)
    srv.submit("b", x)
    srv.step()
    # wave 2: heavy skew to a -> grants move, b re-planned
    for _ in range(8):
        srv.submit("a", x)
    srv.submit("b", x)
    srv.step()
    tel = srv.telemetry()
    assert srv.arbiter.rebalances >= 1
    assert tel["a"]["replans"] + tel["b"]["replans"] >= 1


# --------------------------------------------------------------------------
# Calibration: the server plans, prices demand, and accounts lane time
# under a measurement-derived CalibrationTable (core/calibrate_cost.py)
# --------------------------------------------------------------------------
def test_server_prices_and_accounts_under_calibration(rng):
    from repro.core.calibrate_cost import AffineFit, CalibrationTable
    clear_plan_cache()
    params = _frontend()
    x = rng.normal(size=(12, 12, 6)).astype(np.float32)
    # a table covering EVERY member via the global fallback: each launch
    # predicts a constant 100us -> 9.4e4 cycles, wildly different from
    # the analytical est-cycles, so calibrated accounting is observable
    table = CalibrationTable(
        global_fit=AffineFit(us_per_compute_cycle=0.0, us_per_hbm_byte=0.0,
                             overhead_us=100.0, n_samples=3))
    results = {}
    for cal in (None, table):
        clear_plan_cache()
        srv = AdaptiveServer(ResourceBudget(), policy="static", max_batch=2,
                             calibration=cal)
        srv.register("t", params, (12, 12, 6))
        srv.submit("t", x)
        (c,) = srv.drain()
        results[cal is not None] = (srv, c)
    srv_cal, done = results[True]
    srv_raw, raw = results[False]
    # numerics are calibration-independent — only cost accounting moves
    np.testing.assert_array_equal(np.asarray(done.result),
                                  np.asarray(raw.result))
    assert done.latency != raw.latency
    tel = srv_cal.telemetry()["t"]
    assert tel["calibration_key"] == table.key()
    assert srv_raw.telemetry()["t"]["calibration_key"] is None
    # unit cost (the arbiter's demand weight) is the calibrated price
    tenant = srv_cal.tenants["t"]
    specs = srv_cal._specs(tenant.net, params, (1, 12, 12, 6), "float32",
                           ())
    want = plan_network(specs, srv_cal.budget,
                        calibration=table).calibrated_cycles(table)
    assert tenant.unit_cost == pytest.approx(want)
    # the arbiter knows which cost model its grants are denominated in
    assert srv_cal.arbiter.calibration is table
