"""SLO scheduler: admission, continuous batching, deadline-aware
dispatch (EDF + priority preemption), load shedding, queue-depth caps,
the dual-clock telemetry contract, and state round-trips."""
import jax
import numpy as np
import pytest

from repro.core.resources import ResourceBudget
from repro.models.frontends import init_cnn_frontend
from repro.obs import EVENTS
from repro.runtime import AdaptiveServer, BudgetArbiter, SLOScheduler, SLOSpec

DEVICE = ResourceBudget(vpu_ops_budget=15_000_000)


class FakeWall:
    """Manually advanced monotonic clock."""

    def __init__(self, step: float = 0.0):
        self.t = 0.0
        self.step = step      # auto-advance per reading (0 = manual)

    def __call__(self) -> float:
        t = self.t
        self.t += self.step
        return t

    def advance(self, dt: float) -> None:
        self.t += dt


def _frontend(key=0, channels=(6, 12), d_model=16):
    return init_cnn_frontend(jax.random.PRNGKey(key), channels=channels,
                             d_model=d_model)


def _deployment(wall=None, **slo_kwargs):
    srv = AdaptiveServer(DEVICE, policy="demand", max_batch=4)
    sched = (SLOScheduler(srv, wall=wall) if wall is not None
             else SLOScheduler(srv))
    sched.register("t", _frontend(), (12, 12, 6),
                   slo=SLOSpec(**(slo_kwargs or {"deadline_s": 60.0})))
    return srv, sched


def _sample(rng, shape=(12, 12, 6)):
    return rng.normal(size=shape).astype(np.float32)


# --------------------------------------------------------------------------
# SLOSpec + registration validation
# --------------------------------------------------------------------------
def test_slospec_validates_fields():
    with pytest.raises(ValueError):
        SLOSpec(deadline_s=0.0)
    with pytest.raises(ValueError):
        SLOSpec(deadline_s=-1.0)
    with pytest.raises(ValueError):
        SLOSpec(deadline_s=1.0, max_queue_depth=0)
    spec = SLOSpec(deadline_s=1.0, priority=3, max_queue_depth=2)
    assert (spec.deadline_s, spec.priority, spec.max_queue_depth) \
        == (1.0, 3, 2)


def test_register_requires_slospec_and_submit_validates():
    srv = AdaptiveServer(DEVICE, max_batch=4)
    sched = SLOScheduler(srv)
    with pytest.raises(TypeError):
        sched.register("t", _frontend(), (12, 12, 6), slo=1.5)
    sched.register("t", _frontend(), (12, 12, 6),
                   slo=SLOSpec(deadline_s=1.0))
    rng = np.random.default_rng(0)
    with pytest.raises(KeyError):
        sched.submit("ghost", _sample(rng))
    with pytest.raises(ValueError):
        sched.submit("t", _sample(rng, (8, 8, 3)))


def test_scheduler_refuses_server_with_queued_requests(rng):
    srv = AdaptiveServer(DEVICE, max_batch=4)
    srv.register("t", _frontend(), (12, 12, 6))
    srv.submit("t", _sample(rng))
    with pytest.raises(ValueError):
        SLOScheduler(srv)


# --------------------------------------------------------------------------
# Continuous batching + deferred arrivals
# --------------------------------------------------------------------------
def test_batches_fill_to_max_batch(rng):
    srv, sched = _deployment()
    rids = [sched.submit("t", _sample(rng)) for _ in range(6)]
    comps = sched.run()
    assert len(comps) == 6
    assert sched.launches == 2            # 4 + 2, not 6 singles
    assert all(sched.outcomes[r] == "ok" for r in rids)
    assert sched.pending() == 0


def test_deferred_arrival_waits_for_its_clock(rng):
    srv, sched = _deployment()
    early = sched.submit("t", _sample(rng))
    late = sched.submit("t", _sample(rng), at=sched.now + 1e9)
    comps = sched.run()
    assert len(comps) == 2
    assert sched.launches == 2            # the late arrival missed batch 1
    assert {c.rid for c in comps} == {early, late}
    # the dispatch frontier advanced to the deferred arrival
    assert sched.now >= 1e9


# --------------------------------------------------------------------------
# Deadline-aware dispatch: EDF across buckets, priority preemption
# --------------------------------------------------------------------------
def test_earliest_deadline_jumps_queue_without_priority(rng):
    """Equal priorities: the tighter-deadline bucket launches first —
    an EDF reorder, not a preemption."""
    srv = AdaptiveServer(DEVICE, max_batch=4)
    sched = SLOScheduler(srv)
    sched.register("loose", _frontend(0), (12, 12, 6),
                   slo=SLOSpec(deadline_s=100.0))
    sched.register("tight", _frontend(1), (12, 12, 6),
                   slo=SLOSpec(deadline_s=0.5))
    sched.submit("loose", _sample(rng))
    sched.submit("tight", _sample(rng))
    comps = sched.run()
    assert comps[0].tenant == "tight"
    assert sched.preemptions == 0


def test_priority_preempts_queued_bucket_and_moves_grant(rng):
    EVENTS.clear()
    srv = AdaptiveServer(DEVICE, max_batch=4)
    sched = SLOScheduler(srv)
    sched.register("bulk", _frontend(0), (12, 12, 6),
                   slo=SLOSpec(deadline_s=60.0, priority=0))
    sched.register("rt", _frontend(1), (12, 12, 6),
                   slo=SLOSpec(deadline_s=60.0, priority=2))
    sched.submit("bulk", _sample(rng))       # queued first (FIFO baseline)
    sched.submit("rt", _sample(rng))
    comps = sched.run()
    assert comps[0].tenant == "rt"           # jumped the earlier bucket
    assert sched.preemptions >= 1
    assert srv.tenants["rt"].telemetry.preemptions >= 1
    assert srv.arbiter.preemptions >= 1      # grant actually moved
    evs = EVENTS.recent(kind="scheduler.preempt")
    assert evs and evs[-1]["winner"] == "rt" and evs[-1]["victim"] == "bulk"


# --------------------------------------------------------------------------
# Load shedding + queue-depth caps
# --------------------------------------------------------------------------
def test_expired_requests_are_shed_not_executed(rng):
    EVENTS.clear()
    wall = FakeWall()
    srv, sched = _deployment(wall=wall, deadline_s=0.5)
    rids = [sched.submit("t", _sample(rng)) for _ in range(8)]
    sched.run(max_launches=sched.launches + 1)   # first 4 served at t=0
    wall.advance(1.0)                            # the rest expire queued
    comps = sched.run()
    assert comps == []
    assert sched.sheds == 4
    assert sorted(sched.outcomes[r] for r in rids) \
        == ["ok"] * 4 + ["shed"] * 4
    assert sched.pending() == 0
    assert srv.tenants["t"].telemetry.shed == 4
    assert srv.arbiter.miss_rate("t") > 0.0      # sheds feed the EWMA
    assert EVENTS.recent(kind="scheduler.shed")


def test_max_queue_depth_rejects_overflow(rng):
    srv, sched = _deployment(deadline_s=60.0, max_queue_depth=2)
    rids = [sched.submit("t", _sample(rng)) for _ in range(5)]
    comps = sched.run()
    assert len(comps) == 2
    assert sched.rejections == 3
    outcomes = [sched.outcomes[r] for r in rids]
    assert outcomes.count("rejected") == 3 and outcomes.count("ok") == 2
    assert srv.tenants["t"].telemetry.shed == 3  # rejections count as shed


# --------------------------------------------------------------------------
# Dual-clock contract: est-cycles lanes, wall deadlines — both reported
# --------------------------------------------------------------------------
def test_telemetry_reports_both_clocks(rng):
    srv, sched = _deployment(deadline_s=60.0)
    for _ in range(4):
        sched.submit("t", _sample(rng))
    sched.run()
    snap = srv.tenants["t"].telemetry.snapshot()
    assert snap["p95_cycles"] > 0.0              # modeled est-cycles clock
    assert snap["wall_p95_s"] >= 0.0             # measured wall clock
    assert snap["slo_tracked"] == 4
    assert snap["deadline_misses"] == 0
    assert snap["deadline_miss_rate"] == 0.0


def test_wall_clock_judges_misses_not_the_model_clock(rng):
    # auto-advancing wall + shedding disabled: every request is judged
    # LATE on the wall even though the modeled est-cycles latency is
    # tiny — the dual-clock rule in action
    wall = FakeWall(step=0.1)
    srv = AdaptiveServer(DEVICE, max_batch=4)
    sched = SLOScheduler(srv, wall=wall, shed_margin_s=-1e9)
    sched.register("t", _frontend(), (12, 12, 6),
                   slo=SLOSpec(deadline_s=0.05))
    rids = [sched.submit("t", _sample(rng)) for _ in range(4)]
    comps = sched.run()
    assert len(comps) == 4                       # executed, not shed
    assert all(sched.outcomes[r] == "miss" for r in rids)
    snap = srv.tenants["t"].telemetry.snapshot()
    assert snap["deadline_misses"] == 4
    assert snap["deadline_miss_rate"] == 1.0
    assert srv.arbiter.miss_rate("t") > 0.0


def test_wall_completion_stamped_after_device_wait(rng, monkeypatch):
    """Completion is stamped on the wall clock only after the batch's
    outputs are ready on the device: the (fake) device wait advances the
    clock by 5 s, and every request's wall latency must include it."""
    wall = FakeWall()
    srv, sched = _deployment(wall=wall, deadline_s=60.0)
    waited = []
    real_wait = jax.block_until_ready

    def device_wait(x):
        waited.append(len(x))
        wall.advance(5.0)
        return real_wait(x)

    monkeypatch.setattr(jax, "block_until_ready", device_wait)
    for _ in range(4):
        sched.submit("t", _sample(rng))
    comps = sched.run()
    assert waited == [4]                         # one wait, the whole batch
    assert all(c.ok for c in comps)
    assert list(srv.tenants["t"].telemetry.wall_latencies) == [5.0] * 4


# --------------------------------------------------------------------------
# Arbiter extensions the scheduler rides on
# --------------------------------------------------------------------------
def test_grant_quantum_bounds_budget_key_space():
    arb = BudgetArbiter(ResourceBudget(), rebalance_threshold=0.0,
                        demand_alpha=1.0, grant_quantum=1 / 8)
    arb.register("a", floor=0.05)
    arb.register("b", floor=0.05)
    arb.observe("a", 700.0)
    arb.observe("b", 300.0)
    shares = arb.split()
    for s in shares.values():
        on_grid = abs(s.fraction / (1 / 8) - round(s.fraction / (1 / 8))) \
            < 1e-9
        assert on_grid or s.fraction == pytest.approx(s.floor)
        assert s.fraction >= s.floor
    assert sum(s.fraction for s in shares.values()) <= 1.0 + 1e-9


def test_grant_quantum_validation():
    with pytest.raises(ValueError):
        BudgetArbiter(ResourceBudget(), grant_quantum=1.0)
    with pytest.raises(ValueError):
        BudgetArbiter(ResourceBudget(), grant_quantum=-0.1)


def test_slo_pressure_amplifies_missing_tenant():
    arb = BudgetArbiter(ResourceBudget(), rebalance_threshold=0.0,
                        demand_alpha=1.0, slo_pressure=4.0, miss_alpha=1.0)
    arb.register("a")
    arb.register("b")
    arb.observe("a", 500.0)
    arb.observe("b", 500.0)
    even = arb.split()
    assert even["a"].fraction == pytest.approx(even["b"].fraction)
    arb.observe("a", 500.0)
    arb.observe("b", 500.0)
    arb.record_outcome("a", served=4, missed=4)  # a is missing deadlines
    shares = arb.split()
    assert shares["a"].fraction > shares["b"].fraction


# --------------------------------------------------------------------------
# State round-trip (what a plan-preserving restart carries)
# --------------------------------------------------------------------------
def test_state_dict_roundtrip(rng):
    srv, sched = _deployment(deadline_s=2.5)
    sched.submit("t", _sample(rng))
    sched.run()
    state = sched.state_dict()
    assert state["slos"]["t"]["deadline_s"] == 2.5
    assert state["launches"] == sched.launches

    srv2 = AdaptiveServer(DEVICE, max_batch=4)
    srv2.register("t", _frontend(), (12, 12, 6))
    sched2 = SLOScheduler(srv2)
    sched2.load_state(state)
    assert sched2.slos["t"] == sched.slos["t"]
    assert sched2.launches == sched.launches


def test_load_state_rejects_unregistered_tenant():
    srv = AdaptiveServer(DEVICE, max_batch=4)
    sched = SLOScheduler(srv)
    with pytest.raises(ValueError):
        sched.load_state({"slos": {"ghost": {"deadline_s": 1.0,
                                             "priority": 0,
                                             "max_queue_depth": None}}})
