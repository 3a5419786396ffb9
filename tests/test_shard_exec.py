"""The sharding contract end to end (docs/adaptive_ips.md, "Sharding
contract"): planner decisions (split wins / refusal / rescue), plan
serialization and cache identity across meshes, arbiter whole-device
grants, and sharded execution matching the replicated walk.

Planning is pure — no devices needed — so those tests run in-process.
Execution tests spawn a subprocess with
``XLA_FLAGS=--xla_force_host_platform_device_count=2`` (JAX fixes its
device count at import; the flag must never leak into other tests).
The measured-wall-clock half of the contract lives in
``benchmarks/run.py::table_mesh``.
"""
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

from repro.core import plan as plan_mod
from repro.core.ip import SiteSpec
from repro.core.plan import (NetworkPlan, clear_plan_cache, plan_network,
                             replan)
from repro.core.resources import MeshSpec, ResourceBudget
from repro.core.shard import force_shard_decisions
from repro.runtime.arbiter import BudgetArbiter

REPO = Path(__file__).resolve().parent.parent
MESH2 = MeshSpec(devices=2)
# The MXU ration that gates the MXU member at 1 device — the same
# ration benchmarks/run.py::table_mesh measures under.
WIN_BUDGET = ResourceBudget(mxu_passes_budget=7)


def _conv(name="conv", x=(8, 16, 16, 32), w=(3, 3, 32, 128),
          dtype="float32"):
    return SiteSpec.make(name, "conv2d", (x, w), dtype, dual=False)


def run_sub(body: str, n_dev: int = 2, timeout: int = 420) -> str:
    code = textwrap.dedent(f"""
        import os
        os.environ["XLA_FLAGS"] = (
            "--xla_force_host_platform_device_count={n_dev}")
        import dataclasses
        import jax
        import jax.numpy as jnp
        import numpy as np
    """) + textwrap.dedent(body)
    env = dict(os.environ,
               PYTHONPATH=str(REPO / "src") + os.pathsep
               + os.environ.get("PYTHONPATH", ""))
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=timeout, env=env)
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr}"
    return out.stdout


# --------------------------------------------------------------------------
# Planner decisions
# --------------------------------------------------------------------------
def test_split_wins_flips_member_and_cuts_cycles():
    clear_plan_cache()
    # bf16 rows 32 wide: one MXU pass per tap-row beats the VPU member
    spec = _conv(x=(8, 34, 34, 128), w=(3, 3, 128, 128), dtype="bfloat16")
    p1 = plan_network((spec,), WIN_BUDGET)
    p2 = plan_network((spec,), WIN_BUDGET, mesh=MESH2)
    s1, s2 = p1.sites[0], p2.sites[0]
    assert not s1.sharded
    assert s2.sharded and (s2.shard_axis, s2.shard_degree) == ("batch", 2)
    # the collective bill is in the plan's own cost, not a side channel
    assert s2.footprint.comm_cycles > 0.0
    assert p2.total_cycles < p1.total_cycles
    # halving the per-device batch buys the rationed MXU member back
    assert s1.ip.name.endswith("ip1_vpu")
    assert s2.ip.name.endswith("ip2_mxu")


def test_refusal_when_collectives_dominate():
    # 1x1 conv, tiny compute, 8 MiB output: a chan split would all-
    # reduce the full output at ~11x the site's compute — degree stays 1
    spec = _conv(x=(4, 64, 64, 4), w=(1, 1, 4, 128))
    pr = plan_network((spec,), ResourceBudget(), mesh=MESH2)
    s = pr.sites[0]
    assert not s.sharded and s.shard_degree == 1
    assert s.footprint.comm_cycles == 0.0
    forced = force_shard_decisions((spec,), MESH2, axis="chan")
    assert sum(f.comm_cycles for f in forced) > pr.total_cycles


def test_sharding_rescues_single_device_infeasibility():
    # 600 KiB vmem: no 1-device member fits, but the chan split's
    # halved working set does — the mesh widens feasibility
    spec = _conv()
    tight = ResourceBudget(vmem_bytes=600 * 1024)
    with pytest.raises(ValueError, match="no feasible IP"):
        plan_network((spec,), tight)
    rescued = plan_network((spec,), tight, mesh=MESH2)
    s = rescued.sites[0]
    assert s.sharded and s.shard_degree == 2


def test_single_device_mesh_is_the_trivial_plan():
    spec = _conv("one")
    p = plan_network((spec,), WIN_BUDGET, mesh=MeshSpec(devices=1))
    assert not p.sites[0].sharded
    assert p.sites[0].footprint.comm_cycles == 0.0


# --------------------------------------------------------------------------
# Serialization + cache identity
# --------------------------------------------------------------------------
def test_plan_json_round_trips_sharding_fields():
    p2 = plan_network((_conv("json"),), WIN_BUDGET, mesh=MESH2)
    restored = NetworkPlan.from_json(p2.to_json())
    assert restored == p2
    assert restored.mesh == MESH2
    s = restored.sites[0]
    assert (s.shard_axis, s.shard_degree) == ("batch", 2)
    assert s.footprint.comm_cycles == p2.sites[0].footprint.comm_cycles
    # bit-exact: serialize(deserialize(x)) == x
    assert restored.to_json() == p2.to_json()


def test_plan_cache_keys_on_mesh():
    clear_plan_cache()
    specs = (_conv("cachemesh"),)
    p0 = plan_network(specs, WIN_BUDGET)
    p2 = plan_network(specs, WIN_BUDGET, mesh=MESH2)
    assert p0 is not p2
    keys = [k for k in plan_mod._PLAN_CACHE if k[0] == specs]
    # key layout: (specs, budget, fuse, mesh, calibration_key)
    assert {k[3] for k in keys} == {None, MESH2}
    # exact repeats are O(1) hits returning the same object...
    assert plan_network(specs, WIN_BUDGET, mesh=MESH2) is p2
    # ...and mesh replans route through the same memoized path
    assert replan(specs, WIN_BUDGET, mesh=MESH2) is p2


def test_device_plan_halves_the_sharded_dim():
    p2 = plan_network((_conv("dev"),), WIN_BUDGET, mesh=MESH2)
    dp = p2.device_plan()
    gx = p2.sites[0].spec.shapes[0]
    dx = dp.sites[0].spec.shapes[0]
    assert dx[0] == gx[0] // 2 and dx[1:] == gx[1:]
    # the global plan keeps global shapes — device_plan is a view
    assert p2.sites[0].spec.shapes[0] == gx


# --------------------------------------------------------------------------
# Arbiter whole-device grants
# --------------------------------------------------------------------------
def test_arbiter_grants_partition_the_mesh():
    arb = BudgetArbiter(ResourceBudget(), mesh=MeshSpec(devices=4))
    for name in ("a", "b", "c"):
        arb.register(name)
    arb.observe("a", 6000.0)
    arb.observe("b", 1000.0)
    arb.observe("c", 1000.0)
    shares = arb.split()
    devs = {n: s.devices for n, s in shares.items()}
    # every tenant holds >= 1 whole device and the grants tile the mesh
    assert sum(devs.values()) == 4
    assert all(v >= 1 for v in devs.values())
    assert devs["a"] == 2            # the demand-heavy tenant gets the spare
    # slices are contiguous, ordered by registration, and partition [0, 4)
    slices = [arb.device_slice(n) for n in ("a", "b", "c")]
    assert slices[0][0] == 0 and slices[-1][1] == 4
    for (_, a1), (b0, _) in zip(slices, slices[1:]):
        assert a1 == b0
    for n in devs:
        assert arb.mesh_for(n).devices == devs[n]
        # whole-device grants plan against the FULL per-device budget
        assert arb.budget_for(n) == arb.budget


def test_arbiter_rejects_tenants_beyond_devices():
    arb = BudgetArbiter(ResourceBudget(), mesh=MESH2)
    arb.register("a")
    arb.register("b")
    with pytest.raises(ValueError, match="whole device"):
        arb.register("c")
    # the rejected registration left no ghost state
    assert set(arb.split()) == {"a", "b"}


# --------------------------------------------------------------------------
# Execution (subprocess: 2 forced host devices)
# --------------------------------------------------------------------------
def test_sharded_execution_matches_replicated():
    run_sub("""
        from repro.core.ip import SiteSpec
        from repro.core.plan import plan_network
        from repro.core.resources import MeshSpec, ResourceBudget
        from repro.core.shard import force_shard_decisions
        from repro.distributed.shard_exec import (apply_plan_replicated,
                                                  apply_plan_sharded)
        mesh = MeshSpec(devices=2)
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.normal(size=(8, 16, 16, 32)).astype(np.float32))
        w = jnp.asarray(rng.normal(0, (9 * 32) ** -0.5,
                                   (3, 3, 32, 128)).astype(np.float32))
        spec = SiteSpec.make("conv", "conv2d", (x.shape, w.shape),
                             "float32", dual=False)
        p2 = plan_network((spec,), ResourceBudget(mxu_passes_budget=7),
                          mesh=mesh)
        assert p2.sites[0].shard_axis == "batch"
        y_rep = apply_plan_replicated(p2, x, {"conv": w})
        y_shd = apply_plan_sharded(p2, x, {"conv": w})
        # f32 batch split reorders nothing: bit-identical
        assert (np.asarray(y_rep) == np.asarray(y_shd)).all()

        # chan split: per-device partial sums + all-reduce — equal up to
        # float summation order, for both the psum and the ring path
        force_shard_decisions((spec,), mesh, axis="chan")  # legality
        sites = tuple(dataclasses.replace(s, shard_axis="chan",
                                          shard_degree=2)
                      for s in p2.sites)
        forced = dataclasses.replace(p2, sites=sites, mesh=mesh)
        y_chan = apply_plan_sharded(forced, x, {"conv": w})
        np.testing.assert_allclose(np.asarray(y_chan), np.asarray(y_rep),
                                   rtol=1e-5, atol=1e-5)
        y_ring = apply_plan_sharded(forced, x, {"conv": w}, use_ring=True)
        np.testing.assert_allclose(np.asarray(y_ring), np.asarray(y_chan),
                                   rtol=1e-5, atol=1e-5)
        print("exec OK")
    """)


def test_sharded_fused_chain_matches_replicated():
    run_sub("""
        from repro.core.plan import plan_network
        from repro.core.resources import MeshSpec, ResourceBudget
        from repro.core.shard import force_shard_decisions
        from repro.distributed.shard_exec import (apply_plan_replicated,
                                                  apply_plan_sharded)
        from repro.models.blocks import cnn_block_site_specs
        mesh = MeshSpec(devices=2)
        rng = np.random.default_rng(1)
        x = jnp.asarray(rng.normal(size=(4, 16, 16, 8)).astype(np.float32))
        w = jnp.asarray(rng.normal(0, (9 * 8) ** -0.5,
                                   (3, 3, 8, 16)).astype(np.float32))
        specs, _ = cnn_block_site_specs(x.shape, w.shape,
                                        x_dtype="float32", site="blk")
        pf = plan_network(tuple(specs), ResourceBudget())  # fuses by default
        assert [s.spec.family for s in pf.sites] == ["cnn_fused"]
        gspecs = tuple(s.spec for s in pf.sites)
        force_shard_decisions(gspecs, mesh, axis="batch")  # legality
        sites = tuple(dataclasses.replace(s, shard_axis="batch",
                                          shard_degree=2)
                      for s in pf.sites)
        pff = dataclasses.replace(pf, sites=sites, mesh=mesh)
        weights = {"blk.fused": w}
        y_rep = apply_plan_replicated(pf, x, weights)
        y_shd = apply_plan_sharded(pff, x, weights)
        assert (np.asarray(y_rep) == np.asarray(y_shd)).all()
        print("fused OK")
    """)


def test_unsharded_mesh_plan_runs_on_granted_device():
    """Mesh mode, one device per tenant: each tenant's degree-1 plan runs
    on the device of its own slice, not on the default device."""
    run_sub("""
        from repro.core.resources import MeshSpec, ResourceBudget
        from repro.models.frontends import init_cnn_frontend
        from repro.runtime import AdaptiveServer
        srv = AdaptiveServer(ResourceBudget(), mesh=MeshSpec(devices=2),
                             max_batch=2)
        rng = np.random.default_rng(0)
        for i, name in enumerate(("a", "b")):
            srv.register(name, init_cnn_frontend(
                jax.random.PRNGKey(i), channels=(6, 12), d_model=16),
                (12, 12, 6))
            srv.submit(name, rng.normal(size=(12, 12, 6)).astype(np.float32))
        comps = srv.drain()
        placed = set()
        for c in comps:
            start, stop = srv.arbiter.device_slice(c.tenant)
            assert stop - start == 1
            assert srv.plan_for(c.tenant, 1).sites[0].shard_degree == 1
            assert c.result.devices() == {jax.devices()[start]}
            placed.add(start)
        assert placed == {0, 1}
        print("placed OK")
    """)


def test_sharded_execution_refuses_lowered_plans():
    spec = SiteSpec.make("lo", "conv2d", ((2, 8, 8, 4), (3, 3, 4, 8)),
                         "float32", ladder=(16, 8), dual=False)
    plan = plan_network((spec,), ResourceBudget(vmem_bytes=200 * 1024))
    assert plan.sites[0].lowered
    from repro.distributed.shard_exec import apply_plan_sharded
    with pytest.raises(ValueError, match="float-only"):
        apply_plan_sharded(plan, None)
