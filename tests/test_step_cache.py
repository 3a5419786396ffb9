"""The server's compiled-step cache: one jitted step per execution key
(tenant, batch shape, the plan's per-site choices), the eager paths it
leaves alone, and what a warm launch no longer does (trace, transfer)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.plan import clear_plan_cache
from repro.core.resources import ResourceBudget
from repro.models.frontends import apply_cnn_frontend, init_cnn_frontend
from repro.obs import COMPILES
from repro.runtime import AdaptiveServer, SLOScheduler, SLOSpec
from repro.runtime.faults import INJECTOR, FaultSpec
from repro.runtime.guards import GuardPolicy

SHAPE = (12, 12, 6)
KIB = 1024


def _frontend(key=0):
    return init_cnn_frontend(jax.random.PRNGKey(key), channels=(6, 12),
                             d_model=16)


def _frames(n, seed=0):
    rng = np.random.default_rng(seed)
    return rng.normal(size=(n,) + SHAPE).astype(np.float32)


def _choices(plan):
    return [(s.spec.name, s.ip.name, s.precision_bits) for s in plan.sites]


def _traces():
    return sum(COMPILES.counts("jit.trace").values())


def _scheduled():
    srv = AdaptiveServer(ResourceBudget(), max_batch=4)
    sched = SLOScheduler(srv)
    sched.register("t", _frontend(), SHAPE, slo=SLOSpec(deadline_s=60.0))
    return srv, sched


# (budget, fuse, ladder, activation) -> which kind of plan it serves
PLANS = {
    "fused_f32": (ResourceBudget(), True, (), "relu"),
    "unfused_f32": (ResourceBudget(), False, (), "relu"),
    # conv f32, pool int16, activation int8 (act_lut)
    "unfused_int8": (ResourceBudget(vmem_bytes=1024 * KIB), False,
                     (16, 8), "tanh"),
    "fused_int8": (ResourceBudget(vmem_bytes=300 * KIB), True, (16, 8),
                   "tanh"),
}


def _served_and_eager(kind):
    """One batch of 2 served through the server, and the eager frontend
    on the plan it served."""
    budget, fuse, ladder, activation = PLANS[kind]
    clear_plan_cache()
    params = _frontend()
    srv = AdaptiveServer(budget, max_batch=2, fuse=fuse)
    srv.register("t", params, SHAPE, activation=activation, ladder=ladder)
    xs = _frames(2)
    rids = srv.submit("t", xs)
    comps = {c.rid: c for c in srv.drain()}
    plan = srv.plan_for("t", 2)
    fused = any(s.spec.name.endswith(".fused") for s in plan.sites)
    assert fused == fuse, _choices(plan)
    if ladder:
        assert any(s.precision_bits == 8 for s in plan.sites), \
            _choices(plan)
    tel = srv.telemetry()["t"]
    assert (tel["step_cache_hits"], tel["step_cache_misses"]) == (0, 1)
    want = apply_cnn_frontend(params, jnp.asarray(xs), network=plan,
                              activation=activation, ladder=ladder)
    got = np.stack([np.asarray(comps[rid].result) for rid in rids])
    return got, np.asarray(want)


@pytest.mark.parametrize("kind", ["fused_f32", "unfused_f32",
                                  "unfused_int8"])
def test_compiled_step_equals_eager_frontend_bitwise(kind):
    got, want = _served_and_eager(kind)
    np.testing.assert_array_equal(got, want)


def test_compiled_fused_int8_step_matches_eager_to_rounding():
    """The int8 codes and the kernel are the same; only the combined
    dequantize scale (``act scale * weight scale``, each ``amax / 127``)
    may differ in its last bits, since under ``jax.jit`` XLA rewrites
    that scalar arithmetic (a division by a constant becomes a product
    with its reciprocal).  So the outputs agree to a few float32 steps
    of their magnitude, not bit for bit."""
    got, want = _served_and_eager("fused_int8")
    atol = 8 * np.finfo(np.float32).eps * np.abs(want).max()
    np.testing.assert_allclose(got, want, rtol=0, atol=atol)


def test_warm_launch_traces_nothing_and_hits_the_step_cache():
    clear_plan_cache()
    srv, sched = _scheduled()
    xs = _frames(8)
    sched.submit("t", xs[:4])
    sched.run()
    traces0 = _traces()
    sched.submit("t", xs[4:])
    comps = sched.run()
    assert len(comps) == 4 and all(c.ok for c in comps)
    assert _traces() == traces0
    tel = srv.telemetry()["t"]
    assert (tel["step_cache_hits"], tel["step_cache_misses"]) == (1, 1)
    text = srv.metrics().render()
    assert "repro_serve_step_cache_hits_total 1" in text
    assert "repro_serve_step_cache_misses_total 1" in text


def test_warm_launch_makes_no_host_to_device_transfer():
    clear_plan_cache()
    _srv, sched = _scheduled()
    frames = [jnp.asarray(x) for x in _frames(8)]
    for x in frames[:4]:
        sched.submit("t", x)
    sched.run()
    for x in frames[4:]:
        sched.submit("t", x)
    with jax.transfer_guard_host_to_device("disallow"):
        comps = sched.run()
        jax.block_until_ready([c.result for c in comps])
    assert len(comps) == 4 and all(c.ok for c in comps)


def test_grant_move_keeping_the_plan_reuses_the_step():
    clear_plan_cache()
    srv = AdaptiveServer(ResourceBudget(vpu_ops_budget=15_000_000),
                         policy="demand", max_batch=2,
                         rebalance_threshold=0.05)
    srv.register("a", _frontend(0), SHAPE)
    srv.register("b", _frontend(1), SHAPE)
    x = _frames(1)[0]
    srv.submit("a", x)
    srv.submit("b", x)
    srv.step()
    granted, plan = srv.tenants["b"].granted, _choices(srv.plan_for("b", 1))
    for _ in range(8):                  # skew demand to a: b's grant moves
        srv.submit("a", x)
    srv.submit("b", x)
    srv.step()
    assert srv.tenants["b"].granted != pytest.approx(granted)
    assert _choices(srv.plan_for("b", 1)) == plan
    tel = srv.telemetry()["b"]
    assert tel["replans"] == 1
    assert (tel["step_cache_hits"], tel["step_cache_misses"]) == (1, 1)


def test_budget_move_that_changes_a_site_recompiles():
    clear_plan_cache()
    srv = AdaptiveServer(ResourceBudget(vmem_bytes=4096 * KIB), max_batch=2)
    srv.register("t", _frontend(1), SHAPE, activation="tanh",
                 ladder=(16, 8))
    xs = _frames(2)
    seen = []

    def serve(kib):
        srv.on_budget_shrink(kib * KIB / srv.budget.vmem_bytes)
        srv.submit("t", xs)
        assert all(c.ok for c in srv.drain())
        tel = srv.telemetry()["t"]
        seen.append((_choices(srv.plan_for("t", 2)),
                     tel["step_cache_hits"], tel["step_cache_misses"]))

    for kib in (4096, 3686, 1024, 300):
        serve(kib)
    (p0, _, _), (p1, h1, m1), (p2, h2, m2), (p3, h3, m3) = seen
    assert p1 == p0 and (h1, m1) == (1, 1)        # same choices: a hit
    (ip0,), (ip2,) = [[ip for _, ip, _ in p] for p in (p0, p2)]
    assert ip2 != ip0 and (h2, m2) == (1, 2)      # another IP: a miss
    assert [b for _, _, b in p3] != [b for _, _, b in p2]
    assert (h3, m3) == (1, 3)                     # another width: a miss


def test_measure_quant_tenant_reports_error_through_the_eager_path():
    clear_plan_cache()
    srv = AdaptiveServer(ResourceBudget(vmem_bytes=300 * KIB), max_batch=2)
    srv.register("t", _frontend(1), SHAPE, activation="tanh",
                 ladder=(16, 8), measure_quant=True)
    for _ in range(2):
        srv.submit("t", _frames(2))
        assert all(c.ok for c in srv.drain())
    tel = srv.telemetry()["t"]
    assert tel["lowered_fraction"] > 0.0
    assert 0.0 < tel["max_quant_rel_err"] <= 5e-2
    assert (tel["step_cache_hits"], tel["step_cache_misses"]) == (0, 0)


def test_guard_screens_injected_nan_and_retries_in_f32():
    clear_plan_cache()
    srv = AdaptiveServer(ResourceBudget(vmem_bytes=1024 * KIB), max_batch=2)
    srv.register("t", _frontend(1), SHAPE, activation="tanh",
                 ladder=(16, 8))
    srv.set_guard("t", GuardPolicy(on_nonfinite="retry_f32",
                                   backoff_base_s=0.001))
    with INJECTOR.armed([FaultSpec("nan_output", step=0)]):
        srv.submit("t", _frames(2))
        comps = srv.drain()
    assert len(comps) == 2 and all(c.ok for c in comps)
    for c in comps:
        assert np.isfinite(np.asarray(c.result)).all()
    tel = srv.telemetry()["t"]
    assert tel["guard_retries"] == 1 and tel["guard_rejected"] == 0
    # the laddered attempt and the f32 retry are two execution keys
    assert (tel["step_cache_hits"], tel["step_cache_misses"]) == (0, 2)
    assert set(tel["precision_mix"]) == {32}
