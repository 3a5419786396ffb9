"""Degraded-mesh survival: the degree ladder, post-loss grant previews
(ladder snap), ``BudgetArbiter.on_device_loss``, spare-plan pre-warming
against the exact keys the degraded mesh re-plans under, and the
end-to-end lose-a-device-keep-serving path (subprocess: 2 forced host
devices).  Degradation ordering: the degree ladder descends BEFORE the
precision ladder — survivors keep the full per-device budget, so plans
never lower on a device loss."""
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import pytest

from repro.core.plan import (STATS, clear_plan_cache, plan_cache_contains,
                             plan_network, replan)
from repro.core.resources import MeshSpec, ResourceBudget
from repro.core.shard import degree_ladder
from repro.models.frontends import init_cnn_frontend
from repro.obs import EVENTS
from repro.runtime import AdaptiveServer
from repro.runtime.arbiter import BudgetArbiter
from repro.runtime.recovery import cold_replans_since

REPO = Path(__file__).resolve().parents[1]
DEVICE = ResourceBudget(vpu_ops_budget=15_000_000)


def run_sub(body: str, n_dev: int = 2, timeout: int = 420) -> str:
    code = textwrap.dedent(f"""
        import os
        os.environ["XLA_FLAGS"] = (
            "--xla_force_host_platform_device_count={n_dev}")
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
# The degree ladder
# --------------------------------------------------------------------------
def test_degree_ladder_is_divisors_descending():
    assert degree_ladder(12) == (12, 6, 4, 3, 2, 1)
    assert degree_ladder(1) == (1,)
    assert degree_ladder(7) == (7, 1)


def test_degree_ladder_survivors_filter():
    assert degree_ladder(12, survivors=5) == (4, 3, 2, 1)
    assert degree_ladder(4, survivors=4) == (4, 2, 1)
    # a single survivor always leaves the replicated rung
    assert degree_ladder(16, survivors=1) == (1,)


def test_degree_ladder_validation():
    with pytest.raises(ValueError, match="degree"):
        degree_ladder(0)
    with pytest.raises(ValueError, match="survivors"):
        degree_ladder(4, survivors=0)


def test_every_rung_keeps_batches_tileable():
    """The point of the ladder: any batch size that tiled at the original
    degree still tiles at every rung — no batch shape strands."""
    for degree in (2, 4, 6, 8, 12, 16):
        for batch in range(degree, 4 * degree + 1, degree):
            for rung in degree_ladder(degree):
                assert batch % rung == 0


# --------------------------------------------------------------------------
# Arbiter: post-loss grants
# --------------------------------------------------------------------------
def _mesh_arbiter(devices, tenants=("a", "b")):
    arb = BudgetArbiter(ResourceBudget(), mesh=MeshSpec(devices=devices))
    for name in tenants:
        arb.register(name, 0.05)
    for name in tenants:
        arb.observe(name, 100.0)
    arb.split()
    return arb


def test_degraded_grants_is_a_pure_preview():
    arb = _mesh_arbiter(6)
    before_devices = dict(arb._devices)
    grants = arb.degraded_grants(1)
    assert sum(grants.values()) <= 5
    assert all(g >= 1 for g in grants.values())
    # preview only: nothing moved
    assert arb.mesh.devices == 6 and arb._devices == before_devices


def test_degraded_grants_snap_down_the_ladder():
    """A tenant holding 4 devices that must shrink to 3 lands on 2 — the
    largest divisor of its pre-loss degree — so every batch that sharded
    4-wide still shards."""
    arb = BudgetArbiter(ResourceBudget(), mesh=MeshSpec(devices=5))
    arb.register("big", 0.05)
    arb.register("small", 0.05)
    arb.observe("big", 1000.0)
    arb.observe("small", 1.0)
    arb.split()
    assert arb._devices == {"big": 4, "small": 1}
    grants = arb.degraded_grants(1)
    assert grants["big"] in degree_ladder(4)
    assert grants["small"] >= 1


def test_degraded_grants_refuses_eviction():
    arb = _mesh_arbiter(2)
    with pytest.raises(ValueError, match="at least one whole device"):
        arb.degraded_grants(1)


def test_degraded_grants_is_mesh_only():
    arb = BudgetArbiter(ResourceBudget())
    arb.register("a", 0.1)
    with pytest.raises(ValueError, match="mesh-mode only"):
        arb.degraded_grants(1)
    with pytest.raises(ValueError, match="mesh-mode only"):
        arb.on_device_loss()


def test_on_device_loss_shrinks_and_regrants():
    EVENTS.clear()
    arb = _mesh_arbiter(4)
    rebalances = arb.rebalances
    affected = arb.on_device_loss(3)
    assert arb.mesh.devices <= 3
    assert sum(arb._devices.values()) <= arb.mesh.devices
    assert all(g >= 1 for g in arb._devices.values())
    assert affected                       # someone's grant moved
    assert arb.rebalances == rebalances + 1
    evs = EVENTS.recent(kind="mesh.degraded")
    assert evs and evs[-1]["lost"] == 3


def test_on_device_loss_refuses_eviction():
    arb = _mesh_arbiter(2)
    with pytest.raises(ValueError, match="recover instead"):
        arb.on_device_loss()
    assert arb.mesh.devices == 2          # refused, not half-applied


# --------------------------------------------------------------------------
# Spare-plan pre-warming: the exact keys the degraded mesh asks for
# --------------------------------------------------------------------------
def _mesh_server(max_batch=4):
    srv = AdaptiveServer(DEVICE, mesh=MeshSpec(devices=2),
                         max_batch=max_batch)
    srv.register("a", init_cnn_frontend(jax.random.PRNGKey(0),
                                        channels=(6, 12), d_model=16),
                 (12, 12, 6))
    srv.arbiter.observe("a", 100.0)
    srv._apply_shares(srv.arbiter.split())
    return srv


def test_prewarm_spares_is_mesh_only():
    srv = AdaptiveServer(DEVICE, max_batch=2)
    srv.register("a", init_cnn_frontend(jax.random.PRNGKey(0),
                                        channels=(6, 12), d_model=16),
                 (12, 12, 6))
    with pytest.raises(ValueError, match="mesh-mode only"):
        srv.prewarm_spares()


def test_prewarm_then_degrade_replans_nothing_cold():
    """The headline: pre-warmed spare plans sit under the exact cache
    keys a post-loss re-plan asks for, so degradation is plan-cache-hit
    only.  Pure planning (no sharded execution) — the end-to-end run is
    the subprocess test below."""
    clear_plan_cache()
    srv = _mesh_server(max_batch=4)
    t = srv.tenants["a"]
    # registration warmed b=1 and b=4 (non-mesh, full budget); the
    # intermediate batch shapes are cold until prewarm fills them
    specs_b3 = srv._specs(t.net, t.params, (3,) + t.input_shape, "float32",
                          t.ladder)
    assert not plan_cache_contains(specs_b3, srv.budget, fuse=srv.fuse)
    warmed = srv.prewarm_spares(losses=1)
    assert warmed >= srv.max_batch
    assert plan_cache_contains(specs_b3, srv.budget, fuse=srv.fuse)

    before = STATS.plan_misses
    affected = srv.on_device_loss(1)
    assert affected == ["a"]
    assert srv.mesh.devices == 1 and srv.arbiter.devices_for("a") == 1
    for b in range(1, srv.max_batch + 1):
        specs = srv._specs(t.net, t.params, (b,) + t.input_shape, "float32",
                           t.ladder)
        replan(specs, srv.arbiter.budget_for("a"), fuse=srv.fuse,
               mesh=srv.arbiter.mesh_for("a"))
    assert cold_replans_since(before) == 0
    assert t.telemetry.degradations == 1


def test_degraded_plan_keeps_full_precision():
    """Degree before precision: the surviving device still plans under
    the FULL per-device budget, so a device loss moves the shard degree,
    never the precision bits."""
    srv = _mesh_server(max_batch=2)
    t = srv.tenants["a"]
    specs = srv._specs(t.net, t.params, (2,) + t.input_shape, "float32",
                       t.ladder)
    p2 = plan_network(specs, srv.arbiter.budget_for("a"), fuse=srv.fuse,
                      mesh=srv.arbiter.mesh_for("a"))
    srv.on_device_loss(1)
    p1 = plan_network(specs, srv.arbiter.budget_for("a"), fuse=srv.fuse,
                      mesh=srv.arbiter.mesh_for("a"))
    assert max(s.shard_degree for s in p2.sites) >= 1
    assert all(s.shard_degree == 1 for s in p1.sites)
    assert all(s.precision_bits == 32 for s in p1.sites)
    assert all(not s.lowered for s in p1.sites)


# --------------------------------------------------------------------------
# End to end (subprocess: 2 forced host devices): lose a device mid-
# serving, keep serving
# --------------------------------------------------------------------------
def test_server_survives_device_loss_end_to_end():
    out = run_sub("""
        from repro.core.plan import STATS
        from repro.core.resources import MeshSpec, ResourceBudget
        from repro.models.frontends import init_cnn_frontend
        from repro.runtime import (AdaptiveServer, FaultSpec, GuardPolicy,
                                   INJECTOR)

        srv = AdaptiveServer(ResourceBudget(vpu_ops_budget=15_000_000),
                             mesh=MeshSpec(devices=2), max_batch=2)
        srv.register("a", init_cnn_frontend(jax.random.PRNGKey(0),
                                            channels=(6, 12), d_model=16),
                     (12, 12, 6))
        srv.set_guard("a", GuardPolicy(max_retries=2,
                                       backoff_base_s=0.001))
        rng = np.random.default_rng(0)

        def wave(n=2):
            for _ in range(n):
                srv.submit("a",
                           rng.normal(size=(12, 12, 6)).astype(np.float32))
            return srv.drain()

        healthy = wave()
        assert all(c.ok for c in healthy)
        srv.prewarm_spares(losses=1)

        before = STATS.plan_misses
        # lose the tail device (the convention: surviving slices are
        # contiguous from 0) mid-serving; the guard absorbs the loss
        with INJECTOR.armed([FaultSpec("device_loss", step=0, param=1)]):
            degraded = wave()
        assert all(c.ok for c in degraded), degraded
        assert srv.mesh.devices == 1
        tel = srv.telemetry()["a"]
        assert tel["degradations"] == 1
        assert sorted(tel["shard_degree_mix"]) == [1, 2]
        assert set(tel["precision_mix"]) == {32}   # degree moved, not bits
        print("COLD", STATS.plan_misses - before)
        print("SURVIVED", len(degraded))
    """)
    assert "COLD 0" in out
    assert "SURVIVED 2" in out
