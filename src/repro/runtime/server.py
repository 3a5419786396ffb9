"""AdaptiveServer — multi-tenant serving over the adaptive-IP planner.

The paper's claim is that IPs adapt to the resources *actually
available*; offline that meant one ``plan_network`` call against one
static budget.  This server makes the claim dynamic: several registered
CNN frontends ("tenants") share one device ``ResourceBudget``, a
``BudgetArbiter`` splits it proportional to observed demand (floored at
each tenant's minimal feasible fraction, ladder rungs included), and
when the split shifts the affected tenants are *live re-planned*
through ``core.plan.replan`` — a tenant squeezed below its f32
footprint degrades to int16/int8 execution instead of failing.

Time model: latency is accounted in **estimated cycles**, the same cost
model the planner optimizes.  With ``calibration=`` (a fitted
``core.calibrate_cost.CalibrationTable``) both sides upgrade together:
plans are ranked by measured scale factors and the lane clock advances
by the same calibrated cycles, so grants, telemetry and the planner all
optimize the objective that was actually measured.  Each tenant owns a serving lane (its
spatial slice of the device, the FPGA-region analogy): batches of a
lane execute sequentially, a batch occupies the lane for its plan's
``total_cycles``, and a request's latency is queue wait plus service.
Numerics are real — every batch runs its planned Pallas kernels — only
*time* is modeled, which keeps policies comparable without wall-clock
noise from the interpret-mode substrate.

Requests are shape-bucketed (``batching.py``): same-shaped samples of a
tenant stack into one planned execution, so repeat batch shapes hit the
plan cache with zero selector work.  With ``autotune=True`` the tunable
sites of each executed plan run sweep-chosen tilings
(``core.autotune.plan_tile_overrides``) instead of member defaults.

Each batch runs through a compiled step (``_compiled_step``): one
``jax.jit`` function per execution key — tenant, batch shape and dtype,
ladder, the plan's per-site choice (site, IP, width, lowered or not),
tile overrides and, in mesh mode, the target device — that stacks the
frames, runs the frontend and returns the batch output with its
per-frame rows.  A warm launch therefore traces nothing and moves
nothing from the host; a grant move that keeps every site's choice
re-uses the step.  The weights are arguments of the step, not
constants.  Two cases stay eager: a tenant whose quantization error is
measured (``measure_quant`` with a ladder: the report reads the error on
the host) and a plan served through ``shard_map``
(``_run_frontend_sharded``).  Fault injection, guarded screening and
retry, and the f32 retry's re-plan (another ladder, so another key) act
on the step's outputs, outside it.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.plan import (STATS, network_min_fraction, plan_network,
                             replan)
from repro.core.resources import MeshSpec, ResourceBudget, check_device
from repro.models.frontends import (ChainNet, apply_cnn_frontend,
                                    apply_resnet_frontend)
from repro.obs.trace import NOOP_SPAN, TRACER, log_event
from repro.runtime.arbiter import BudgetArbiter, TenantShare
from repro.runtime.batching import Request, ShapeBucketQueue
from repro.runtime.faults import INJECTOR, InjectedFault
from repro.runtime.guards import GuardPolicy, execute_guarded
from repro.runtime.telemetry import TenantTelemetry

_SIDE_CACHE_MAX = 256   # bound for the tile-, specs- and step-caches


def serve_frontend(net, params, images, **kwargs):
    """``net``'s frontend on ``images``: this module's
    ``apply_resnet_frontend`` for a residual network, its
    ``apply_cnn_frontend`` for a chain, with the description's own
    arguments.  The entry is read from the module when called, so a
    caller may put a wrapper in its place for every batch served."""
    entry = (apply_resnet_frontend if net.kind == "residual"
             else apply_cnn_frontend)
    return entry(params, images, **net.apply_kwargs(), **kwargs)


@dataclasses.dataclass
class Tenant:
    """One registered CNN frontend and its serving state."""

    name: str
    params: Any
    input_shape: Tuple[int, ...]        # per-sample (H, W, C)
    net: Any                            # ChainNet | ResidualNet
    ladder: Tuple[int, ...]
    measure_quant: bool
    floor: float                        # min feasible device fraction
    unit_cost: float                    # est-cycles of one request, ample
    granted: float = 0.0                # current device fraction
    lane_free: float = 0.0              # when this lane next idles (cycles)
    telemetry: TenantTelemetry = None


@dataclasses.dataclass(frozen=True)
class Completion:
    """One served request: result + accounting.  ``ok=False`` means the
    execution guard gave the batch up (rejected or shed) — ``result`` is
    None and the lane did not advance."""

    rid: int
    tenant: str
    result: Any                         # what the tenant's network
                                        # returns for one frame: a chain's
                                        # (S, d_model) patch embeddings, a
                                        # residual net's (classes,) logits
    arrival: float
    finished: float
    batch_size: int
    ok: bool = True

    @property
    def latency(self) -> float:
        return self.finished - self.arrival


class AdaptiveServer:
    """Admit, batch, arbitrate, re-plan, execute.  See module docstring.

    ``policy="demand"`` arbitrates; ``policy="static"`` is the even-split
    baseline.  ``autotune=True`` swaps member-default tilings for
    sweep-chosen ones on the tunable sites of every executed plan.
    """

    def __init__(self, budget: Optional[ResourceBudget] = None, *,
                 policy: str = "demand", rebalance_threshold: float = 0.05,
                 max_batch: int = 4, autotune: bool = False,
                 demand_alpha: float = 0.5,
                 fuse: bool = True, calibration=None,
                 mesh: Optional[MeshSpec] = None,
                 slo_pressure: float = 0.0, miss_alpha: float = 0.5,
                 grant_quantum: float = 0.0):
        check_device()
        self.budget = budget or ResourceBudget()
        # fuse (default True): serve every tenant through fusion-aware
        # plans — a block the planner can fuse runs conv->pool->act as
        # ONE launch, falling back per block when the fused footprint
        # won't fit the tenant's slice.  fuse=False opts out.
        self.fuse = fuse
        # calibration: a fitted CalibrationTable prices every planning
        # decision, the demand weights, and the lane time model in
        # measured scale factors instead of the raw analytical cycles
        # (see core/calibrate_cost.py).  None keeps the analytical model.
        self.calibration = calibration
        # mesh: a MeshSpec with devices > 1 puts the arbiter in mesh
        # mode — tenants are granted whole-device slices and each batch
        # is planned with plan_network(mesh=<tenant sub-mesh>), so a
        # tenant holding several devices may serve *sharded* plans
        # (executed through shard_map when the layout is uniform; see
        # _execute).  None keeps the fractional single-chip server.
        # slo_pressure > 0 makes the arbiter chase deadline-miss EWMAs
        # on top of demand — only meaningful under the SLO scheduler
        # (``runtime/scheduler.py``), which feeds ``record_outcome``.
        self.arbiter = BudgetArbiter(self.budget, policy=policy,
                                     rebalance_threshold=rebalance_threshold,
                                     demand_alpha=demand_alpha,
                                     calibration=calibration, mesh=mesh,
                                     slo_pressure=slo_pressure,
                                     miss_alpha=miss_alpha,
                                     grant_quantum=grant_quantum)
        self.mesh = self.arbiter.mesh
        self.max_batch = max_batch
        self.autotune = autotune
        self.clock = 0.0
        self.tenants: Dict[str, Tenant] = {}
        self._queue = ShapeBucketQueue()
        self._shares: Dict[str, TenantShare] = {}
        # opt-in per-tenant survival policies (runtime/guards.py); a
        # tenant without one executes bare — faults propagate
        self._guards: Dict[str, GuardPolicy] = {}
        self._tile_cache: Dict[tuple, dict] = {}
        # bucket key -> site specs: spec construction runs jax.eval_shape
        # per block, so hot repeat buckets must not rebuild them
        self._specs_cache: Dict[tuple, tuple] = {}
        # execution key -> jitted serving step (_compiled_step)
        self._step_cache: Dict[tuple, Any] = {}
        self._next_rid = 0

    # -- admission ----------------------------------------------------------
    def register(self, name: str, params, input_shape, *, net=None,
                 pool_window=(2, 2), activation: str = "relu",
                 ladder: Tuple[int, ...] = (),
                 measure_quant: bool = False) -> Tenant:
        """Register a CNN frontend as a tenant.

        ``net`` describes the network (``models/frontends.py``:
        ``ChainNet`` or ``ResidualNet``): its site specs at a batch
        shape and the arguments its frontend runs a plan with.  Without
        it the tenant is the chain ``ChainNet(pool_window, activation)``.
        A residual network is served on one device: a mesh server
        refuses it.

        Prices the tenant up front: its *floor* (minimal feasible device
        fraction at max batch, ladder included — what the arbiter must
        always grant) and its *unit cost* (est-cycles of a one-sample
        plan under the full device, the demand weight).  Raises the
        planner's error when the tenant cannot run even with the whole
        device to itself — admission fails honestly.
        """
        if name in self.tenants:
            raise ValueError(f"tenant {name!r} already registered")
        if net is None:
            net = ChainNet(tuple(pool_window), activation)
        if self.mesh is not None and net.kind != ChainNet.kind:
            raise ValueError(
                f"tenant {name!r}: a {net.kind} network is served on one "
                f"device; sharded execution handles chains only")
        input_shape = tuple(int(d) for d in input_shape)
        canonical = self._specs(net, params, (self.max_batch,) + input_shape,
                                "float32", ladder)
        # Admission check: both the max-batch and the one-sample graphs
        # must plan under the full device (raises the planner's
        # canonical error otherwise) — and both plans warm the share
        # cache for the replan fast path.  The floor stays priced on the
        # unfused graph: fusion-aware planning always falls back to the
        # three-site chain, so the unfused minimum remains the sound
        # feasibility guarantee the arbiter must honor.
        plan_network(canonical, self.budget, fuse=self.fuse,
                     calibration=self.calibration)
        floor = network_min_fraction(canonical, self.budget)
        unit = plan_network(
            self._specs(net, params, (1,) + input_shape, "float32", ladder),
            self.budget, fuse=self.fuse,
            calibration=self.calibration).calibrated_cycles(self.calibration)
        tenant = Tenant(name=name, params=params, input_shape=input_shape,
                        net=net, ladder=tuple(ladder),
                        measure_quant=measure_quant,
                        floor=floor, unit_cost=unit,
                        telemetry=TenantTelemetry(name=name,
                                                  max_batch=self.max_batch))
        self.arbiter.register(name, floor)
        self.tenants[name] = tenant
        return tenant

    def set_guard(self, name: str,
                  policy: Optional[GuardPolicy]) -> None:
        """Opt tenant ``name`` into guarded execution (output screening
        + bounded deadline-aware retry + degrade-on-device-loss; see
        ``runtime/guards.py``).  ``None`` clears the policy — the tenant
        executes bare again and faults propagate to the caller."""
        if name not in self.tenants:
            raise KeyError(f"tenant {name!r} is not registered")
        if policy is None:
            self._guards.pop(name, None)
        else:
            self._guards[name] = policy

    def guard_for(self, name: str) -> Optional[GuardPolicy]:
        return self._guards.get(name)

    @staticmethod
    def _specs(net, params, batch_shape, dtype, ladder):
        return net.site_specs(params, batch_shape, dtype, tuple(ladder))

    def submit(self, name: str, x, *, at: Optional[float] = None):
        """Queue one sample (H, W, C) — or a (B, H, W, C) stack, queued
        as B independent requests — arriving at clock ``at`` (default:
        now).  Returns the request id (or list of ids)."""
        tenant = self.tenants[name]
        x = jnp.asarray(x)
        if x.ndim == len(tenant.input_shape) + 1:
            return [self.submit(name, xi, at=at) for xi in x]
        if x.shape != tenant.input_shape:
            raise ValueError(
                f"tenant {name!r} expects samples of shape "
                f"{tenant.input_shape}, got {x.shape}")
        arrival = self.clock if at is None else float(at)
        rid = self._next_rid
        self._next_rid += 1
        self._queue.push(Request(rid=rid, tenant=name, x=x, arrival=arrival))
        self.arbiter.observe(name, tenant.unit_cost)
        return rid

    # -- serving ------------------------------------------------------------
    def step(self) -> List[Completion]:
        """One serving round: arbitrate, then drain every bucket.

        Re-grants move tenant budget slices; a moved slice re-plans the
        tenant's graphs on their next batch (the ``replan`` fast path —
        counted in telemetry as a re-plan when the tenant had already
        been granted before).
        """
        if not self._queue:
            return []
        self._rebalance()
        completions: List[Completion] = []
        for key in self._queue.keys():
            while True:
                batch = self._queue.pop_batch(key, self.max_batch)
                if not batch:
                    break
                completions.extend(self._execute(batch))
        if completions:
            self.clock = max(self.clock,
                             max(c.finished for c in completions))
        return completions

    def _rebalance(self, launch: int = -1) -> None:
        """One arbitration round: ``BudgetArbiter.split`` and its grants
        adopted.  ``launch`` is the SLO scheduler's launch count (-1
        outside it), a stat of the span."""
        with (TRACER.span("arbiter.split", launch=launch,
                          tenants=len(self.tenants))
              if TRACER.enabled else NOOP_SPAN):
            self._apply_shares(self.arbiter.split())

    def _apply_shares(self, shares: Dict[str, TenantShare]) -> None:
        """Adopt one arbitration round's grants.  A moved grant changes
        the tenant's slice budget, which re-plans its graphs on the next
        batch — counted as a re-plan when the tenant had already been
        granted before.  Shared by ``step`` and the SLO scheduler
        (``runtime/scheduler.py``), so both loops account grant moves
        identically."""
        self._shares = shares
        for name, share in shares.items():
            t = self.tenants[name]
            if t.granted and abs(share.fraction - t.granted) > 1e-12:
                t.telemetry.replans += 1
            t.granted = share.fraction

    def drain(self, max_steps: int = 1000) -> List[Completion]:
        out: List[Completion] = []
        for _ in range(max_steps):
            if not self._queue:
                break
            out.extend(self.step())
        return out

    def _execute(self, batch: List[Request], *,
                 deadline_budget_s: Optional[float] = None,
                 launch: int = -1) -> List[Completion]:
        """Run one batch.  ``launch`` is the SLO scheduler's launch
        count (-1 outside it); every span of the batch carries it."""
        # Tracing contract: with no profiler recording, each span site
        # costs one is_enabled() call and one branch — no stats dicts,
        # no span objects (NOOP_SPAN is the shared singleton).
        with (TRACER.span("serve.execute", launch=launch,
                          tenant=batch[0].tenant, batch=len(batch))
              if TRACER.enabled else NOOP_SPAN):
            return self._execute_batch(batch,
                                       deadline_budget_s=deadline_budget_s,
                                       launch=launch)

    def _tenant_budget(self, tenant: Tenant):
        if self.mesh is not None:
            # mesh mode: the tenant holds whole devices — plan against
            # the FULL per-device budget and let the planner decide how
            # (whether) to shard across the granted sub-mesh.
            return (self.arbiter.budget_for(tenant.name),
                    self.arbiter.mesh_for(tenant.name))
        return self.budget.scaled(tenant.granted), None

    def _route_execute_faults(self, tenant: Tenant) -> None:
        """Injection seam "execute": apply the faults due at this batch
        — device loss marks the corpse, budget shrink scales the device
        budget, a kernel exception raises (last, so co-scheduled faults
        still land)."""
        boom = None
        for f in INJECTOR.poll("execute", tenant.name):
            if f.kind == "device_loss":
                INJECTOR.lose(int(f.param))
            elif f.kind == "budget_shrink":
                self.on_budget_shrink(f.param if f.param > 0 else 0.5)
            elif f.kind == "kernel_exception":
                boom = InjectedFault(
                    f"injected kernel-launch failure "
                    f"(tenant {tenant.name!r})")
        if boom is not None:
            raise boom

    def _plan(self, tenant: Tenant, batch_shape, dtype, ladder):
        """Plan one batch shape under the tenant's *current* slice.
        Returns ``(specs, slice_budget, tenant_mesh, plan)``."""
        slice_budget, tenant_mesh = self._tenant_budget(tenant)
        skey = (tenant.name, tuple(batch_shape), str(dtype), ladder)
        specs = self._specs_cache.get(skey)
        if specs is None:
            specs = self._specs(tenant.net, tenant.params, batch_shape,
                                dtype, ladder)
            if len(self._specs_cache) >= _SIDE_CACHE_MAX:
                self._specs_cache.pop(next(iter(self._specs_cache)))
            self._specs_cache[skey] = specs
        plan = replan(specs, slice_budget, fuse=self.fuse,
                      calibration=self.calibration, mesh=tenant_mesh)
        tenant.telemetry.plans[batch_shape[0]] = plan
        return specs, slice_budget, tenant_mesh, plan

    def plan_for(self, name: str, batch: int):
        """The plan a float32 batch of ``batch`` samples of tenant
        ``name`` runs under its current grant (a plan-cache hit once
        such a batch has been served)."""
        tenant = self.tenants[name]
        return self._plan(tenant, (batch,) + tenant.input_shape,
                          jnp.dtype("float32"), tenant.ladder)[3]

    def _attempt(self, tenant: Tenant, xs, *, retry_f32: bool = False,
                 launch: int = -1):
        """One execution attempt: route injected faults, (re)plan under
        the tenant's *current* slice — a degraded mesh re-plans here —
        run the kernels, screen hooks applied by the caller.  ``xs`` is
        the batch's frames.  Returns ``(y, rows, plan, quant_err)``:
        ``rows`` holds ``y``'s per-frame outputs when the compiled step
        produced them, else None.  ``retry_f32=True`` plans with the
        precision ladder off (the guard's non-finite fallback)."""
        if INJECTOR.enabled:
            self._route_execute_faults(tenant)
        ladder = () if retry_f32 else tenant.ladder
        batch_shape = (len(xs),) + tuple(xs[0].shape)
        dtype = xs[0].dtype
        with (TRACER.span("serve.plan", launch=launch)
              if TRACER.enabled else NOOP_SPAN):
            specs, slice_budget, tenant_mesh, plan = self._plan(
                tenant, batch_shape, dtype, ladder)
        if INJECTOR.enabled and tenant_mesh is not None:
            INJECTOR.check_devices(*self.arbiter.device_slice(tenant.name))
        tile_overrides = None
        if self.autotune:
            tkey = (specs, slice_budget)
            tile_overrides = self._tile_cache.get(tkey)
            if tile_overrides is None:
                from repro.core.autotune import plan_tile_overrides
                tile_overrides = plan_tile_overrides(plan)
                if len(self._tile_cache) >= _SIDE_CACHE_MAX:
                    self._tile_cache.pop(next(iter(self._tile_cache)))
                self._tile_cache[tkey] = tile_overrides
        quant_report = {} if (ladder and tenant.measure_quant) else None
        sharded = self._shardable(plan, len(xs))
        device = (self._granted_device(tenant)
                  if tenant_mesh is not None and not sharded else None)
        step = compiled = None
        if quant_report is None and not sharded:
            step, compiled = self._compiled_step(
                tenant, plan, batch_shape, dtype, ladder, tile_overrides,
                device)
        rows = None
        with (TRACER.span("serve.dispatch", launch=launch,
                          launches=plan.total_launches, sharded=sharded,
                          compiled=compiled or "eager")
              if TRACER.enabled else NOOP_SPAN):
            if step is not None:
                if device is not None:
                    xs = jax.device_put(xs, device)
                y, rows = step(tenant.params, xs)
            elif sharded:
                y = self._run_frontend_sharded(
                    tenant, jnp.stack(xs), plan,
                    tile_overrides=tile_overrides)
            else:       # measure_quant: the report reads errors eagerly
                xb = jnp.stack(xs)
                if device is not None:
                    xb = jax.device_put(xb, device)
                y = serve_frontend(tenant.net, tenant.params, xb,
                                   network=plan, ladder=ladder,
                                   quant_report=quant_report,
                                   tile_overrides=tile_overrides,
                                   fuse=self.fuse)
        if INJECTOR.enabled:
            poisoned = INJECTOR.perturb_output("output", y, tenant.name)
            if poisoned is not y:
                y, rows = poisoned, None
        quant_err = 0.0
        if quant_report:
            from repro.quant.report import max_rel_error
            quant_err = max_rel_error(quant_report)
        return y, rows, plan, quant_err

    def _compiled_step(self, tenant: Tenant, plan, batch_shape, dtype,
                       ladder, tile_overrides, device):
        """The jitted step that serves ``plan`` at one batch shape:
        stack the frames, run the frontend on the plan's sites, return
        the batch output and its per-frame rows.  Keyed on what
        execution reads — the plan's per-site choices, not the budget
        that produced them — so a grant move that keeps every choice
        re-uses the compiled step.  The tenant fixes the weights' shapes
        and its network description.  The weights are arguments,
        not constants of the executable.  Returns
        ``(step, "hit"|"miss")``."""
        key = (tenant.name, batch_shape, str(dtype), ladder,
               tuple((s.spec.name, s.ip.name, s.precision_bits, s.lowered)
                     for s in plan.sites),
               None if tile_overrides is None else tuple(
                   (site, tuple(sorted(kw.items())))
                   for site, kw in sorted(tile_overrides.items())),
               device)
        step = self._step_cache.get(key)
        if step is not None:
            tenant.telemetry.step_cache_hits += 1
            return step, "hit"
        tenant.telemetry.step_cache_misses += 1
        net = tenant.net

        def run(params, xs):
            y = serve_frontend(net, params, jnp.stack(xs), network=plan,
                               ladder=ladder, tile_overrides=tile_overrides)
            return y, tuple(y[i] for i in range(len(xs)))

        step = jax.jit(run)
        if len(self._step_cache) >= _SIDE_CACHE_MAX:
            self._step_cache.pop(next(iter(self._step_cache)))
        self._step_cache[key] = step
        return step, "miss"

    def _execute_batch(self, batch: List[Request], *,
                       deadline_budget_s: Optional[float] = None,
                       launch: int = -1) -> List[Completion]:
        tenant = self.tenants[batch[0].tenant]
        with (TRACER.span("serve.stack", launch=launch, batch=len(batch))
              if TRACER.enabled else NOOP_SPAN):
            xs = tuple(r.x for r in batch)
        hits0, misses0 = STATS.plan_hits, STATS.plan_misses
        policy = self._guards.get(tenant.name)
        out: Dict[str, Any] = {}

        def attempt(retry_f32: bool = False):
            y, rows, plan, qerr = self._attempt(
                tenant, xs, retry_f32=retry_f32, launch=launch)
            out["rows"], out["plan"], out["quant_err"] = rows, plan, qerr
            return y

        if policy is None:
            y = attempt()
            report = None
        else:
            y, report = execute_guarded(
                attempt, policy, tenant=tenant.name,
                remaining_s=deadline_budget_s,
                on_device_loss=lambda e: self.on_device_loss(e.device))
            tenant.telemetry.guard_retries += report.retries
        if y is None:
            # the guard gave the batch up: failed completions, lane not
            # advanced, no record_batch (there is no plan bill to pay)
            if report.outcome == "shed":
                tenant.telemetry.guard_shed += len(batch)
            else:
                tenant.telemetry.guard_rejected += len(batch)
            start = max(tenant.lane_free, max(r.arrival for r in batch))
            return [Completion(rid=r.rid, tenant=r.tenant, result=None,
                               arrival=r.arrival, finished=start,
                               batch_size=len(batch), ok=False)
                    for r in batch]
        with (TRACER.span("serve.results", launch=launch, batch=len(batch))
              if TRACER.enabled else NOOP_SPAN):
            rows, plan, quant_err = out["rows"], out["plan"], out["quant_err"]
            start = max(tenant.lane_free, max(r.arrival for r in batch))
            service = plan.calibrated_cycles(self.calibration)
            if INJECTOR.enabled:
                service = INJECTOR.scale_latency(service, tenant.name)
            finish = start + service
            tenant.lane_free = finish
            latencies = [finish - r.arrival for r in batch]
            tenant.telemetry.record_batch(
                len(batch), latencies, plan,
                cache_hits=STATS.plan_hits - hits0,
                cache_misses=STATS.plan_misses - misses0,
                quant_err=quant_err)
            if rows is None:
                rows = [y[i] for i in range(len(batch))]
            return [Completion(rid=r.rid, tenant=r.tenant, result=row,
                               arrival=r.arrival, finished=finish,
                               batch_size=len(batch))
                    for r, row in zip(batch, rows)]

    @staticmethod
    def _shardable(plan, batch: int) -> bool:
        """True when the plan can run through the shard_map frontend
        path: a mesh plan whose sites are ALL batch-sharded at the mesh
        degree (a uniform layout needs no mid-chain relays inside the
        frontend walk), float precision, and a batch that tiles evenly.
        Mixed/chan/degree-1 layouts run the replicated walk of the same
        plan on the first device of the tenant's slice
        (``_granted_device``) — identical math, the mesh then only
        reshapes the time model."""
        if plan.mesh is None or plan.mesh.devices <= 1:
            return False
        d = plan.mesh.devices
        sharded = plan.sharded_sites()
        if len(sharded) != len(plan.sites):
            return False
        if any(s.shard_axis != "batch" or s.shard_degree != d
               or s.lowered for s in plan.sites):
            return False
        return batch % d == 0

    def _granted_device(self, tenant: Tenant):
        """The first device of the tenant's granted slice, where a mesh
        plan that is not uniformly batch-sharded runs.  A slice past the
        devices this process has is refused."""
        start, _stop = self.arbiter.device_slice(tenant.name)
        devices = jax.devices()
        if start >= len(devices):
            raise ValueError(
                f"tenant {tenant.name!r} is granted device {start} but "
                f"only {len(devices)} exist")
        return devices[start]

    def _run_frontend_sharded(self, tenant: Tenant, xb, plan,
                              *, tile_overrides=None):
        """The whole frontend under one shard_map over the tenant's
        device slice: each device runs the per-device plan
        (``plan.device_plan()``) on its batch block; ``out_specs``
        re-tiles the result so the caller sees the replicated contract.
        Bit-identical to the replicated walk for batch sharding (tests
        assert it).  The ``jax.sharding.Mesh`` over the tenant's device
        slice comes from ``fault_tolerance.elastic_remesh`` — the same
        builder the degraded path re-meshes through after a device
        loss."""
        from jax import shard_map
        from jax.sharding import PartitionSpec as P
        from repro.runtime.fault_tolerance import elastic_remesh
        d = plan.mesh.devices
        start, _stop = self.arbiter.device_slice(tenant.name)
        mesh = elastic_remesh(d, axis=plan.mesh.axis, offset=start)
        dplan = plan.device_plan()

        def device_fn(xg):
            return serve_frontend(tenant.net, tenant.params, xg,
                                  network=dplan,
                                  tile_overrides=tile_overrides)

        fn = shard_map(device_fn, mesh=mesh,
                       in_specs=(P(plan.mesh.axis),),
                       out_specs=P(plan.mesh.axis), check_vma=False)
        y = fn(xb)
        if INJECTOR.enabled:
            # injection seam "collective": the gathered result of a
            # sharded execution (corruption lands after the collective)
            y = INJECTOR.perturb_output("collective", y, tenant.name)
        return y

    # -- degraded mesh / fault survival --------------------------------------
    def on_device_loss(self, device: Optional[int] = None) -> list:
        """Degrade, don't die: shrink the mesh by one device
        (``BudgetArbiter.on_device_loss``) and mark the affected tenants
        — their next batch re-plans at the shrunk shard degree (the
        degree ladder descends; precision is untouched because every
        surviving device still plans under the FULL per-device budget).
        Returns the affected tenant names."""
        affected = self.arbiter.on_device_loss(device)
        self.mesh = self.arbiter.mesh
        for name in affected:
            self.tenants[name].telemetry.degradations += 1
        return affected

    def on_budget_shrink(self, fraction: float) -> None:
        """Mid-serving budget shock: the device budget scales to
        ``fraction`` of itself (every tenant's slice shrinks with it at
        its next batch — the precision ladder absorbs what the smaller
        envelope cannot fit)."""
        if not 0.0 < fraction <= 1.0:
            raise ValueError("fraction must be in (0, 1]")
        self.budget = self.budget.scaled(fraction)
        self.arbiter.budget = self.budget
        log_event("budget.shrunk", fraction=fraction)

    def prewarm_spares(self, losses: int = 1) -> int:
        """Pre-plan every tenant's graphs against the post-loss device
        grants (``BudgetArbiter.degraded_grants``), so a real device
        loss re-plans **zero graphs cold** — the spare plans already sit
        in the cache under the exact keys the degraded mesh will ask
        for.  Mesh mode only.  Returns the number of spare plans
        warmed (cache hits included: warm is warm)."""
        if self.mesh is None:
            raise ValueError("prewarm_spares() is mesh-mode only")
        grants = self.arbiter.degraded_grants(losses)
        survivors = self.mesh.devices - int(losses)
        # the post-loss split() may also re-settle by plain largest
        # remainder (no ladder snap) — warm those grants too
        resettle = self.arbiter._device_grants(
            self.arbiter._granted, devices=survivors)
        warmed = 0
        for name, tenant in self.tenants.items():
            degrees = {grants.get(name, 0), resettle.get(name, 0)} - {0}
            for n_dev in degrees:
                spare_mesh = dataclasses.replace(self.arbiter.mesh,
                                                 devices=n_dev)
                for b in range(1, self.max_batch + 1):
                    specs = self._specs(
                        tenant.net, tenant.params, (b,) + tenant.input_shape,
                        "float32", tenant.ladder)
                    plan_network(specs, self.budget, fuse=self.fuse,
                                 calibration=self.calibration,
                                 mesh=spare_mesh if n_dev > 1 else None)
                    warmed += 1
        log_event("mesh.spares_prewarmed", losses=losses, plans=warmed)
        return warmed

    # -- observability ------------------------------------------------------
    def shares(self) -> Dict[str, TenantShare]:
        """The latest arbitration round's grants (empty before a step)."""
        return dict(self._shares)

    def pending(self) -> int:
        return len(self._queue)

    def queue_stats(self) -> Dict[str, int]:
        """Lifetime counters of the shape-bucket queue."""
        return self._queue.stats()

    def metrics(self, registry=None):
        """This server's state folded into a ``MetricsRegistry``
        (``repro.obs.metrics``): planner/cache counters, event log,
        JAX compile counts, arbiter rebalances, and per-tenant telemetry
        including shard degree and comm-cycles share.  Render with
        ``.render()`` (Prometheus text) or ``.snapshot()``."""
        from repro.obs.metrics import system_metrics
        return system_metrics(server=self, registry=registry)

    def telemetry(self) -> Dict[str, dict]:
        """Per-tenant snapshot: latency percentiles (est-cycles),
        batch occupancy, precision mix, re-plans, plan-cache hit rate,
        measured quantization error, and the current grant/floor.
        ``calibration_key`` identifies the cost model the plans and the
        time accounting were priced under (None = analytical)."""
        from repro.core.calibrate_cost import calibration_key
        calkey = calibration_key(self.calibration)
        out = {}
        for name, t in self.tenants.items():
            snap = t.telemetry.snapshot()
            snap["granted_fraction"] = t.granted
            snap["floor_fraction"] = t.floor
            snap["unit_cost_cycles"] = t.unit_cost
            snap["calibration_key"] = calkey
            out[name] = snap
        return out
