"""Execute a mesh-sharded NetworkPlan — the lowering half of the
"Sharding contract" (docs/adaptive_ips.md).

``core/shard.py`` decides *whether* each site splits; this module makes
the split real: one ``shard_map`` over the whole site chain, inside
which every device

* slices its block of the activation when the incoming layout is
  replicated and the site wants a batch/channel shard (free — the data
  is already everywhere),
* all-gathers when a sharded layout must change (the priced boundary
  transitions),
* runs the site's planned member on its per-device block through the
  family ops entry (the same kernels the replicated path runs — the
  plan picked them, sharding must not change the math), and
* for a channel-split conv, all-reduces the partial outputs
  (``psum`` reference, or the explicit ``ring_all_reduce`` ppermute
  path with ``use_ring=True``).

The network's input arrives replicated and its output returns
replicated, so the caller sees exactly the replicated path's contract;
for float32 plans the batch-sharded result is bit-identical and the
channel-split result differs only by float summation order (tests
assert both).  Lowered (quantized) sites are refused — the sharded
executor is a float-precision path.

Multi-device is real in CI via ``XLA_FLAGS=--xla_force_host_platform_
device_count=N`` (see ``launch/mesh.make_host_mesh``); Pallas interpret
-mode kernels compose with ``shard_map`` on host devices.
"""
from __future__ import annotations

from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.plan import NetworkPlan, PlannedSite
from repro.core.shard import FULL, output_layout, required_input_layout
from repro.obs.trace import NOOP_SPAN, TRACER
from repro.runtime.faults import INJECTOR

_CHAIN_FAMILIES = ("conv2d", "pool2d", "activation", "cnn_fused")


def _check_chain(plan: NetworkPlan) -> None:
    for s in plan.sites:
        if s.spec.family not in _CHAIN_FAMILIES:
            raise ValueError(
                f"site {s.spec.name!r} ({s.spec.family}) is not part of a "
                f"conv/pool/act chain; sharded execution handles "
                f"{_CHAIN_FAMILIES}")
        if s.lowered:
            raise ValueError(
                f"site {s.spec.name!r} was lowered to int"
                f"{s.precision_bits}; sharded execution is float-only — "
                "plan without a ladder or without a mesh")


def _run_site(site: PlannedSite, x: jnp.ndarray, w: Optional[jnp.ndarray],
              *, reduce_axis: Optional[str] = None,
              use_ring: bool = False) -> jnp.ndarray:
    """One site through its planned member's ops entry — shared by the
    replicated and the per-device walks (the per-device walk passes
    ``reduce_axis`` for channel-split convs)."""
    spec = site.spec
    if spec.family == "conv2d":
        from repro.kernels.conv2d.ops import conv2d
        return conv2d(x, w, ip=site.ip.name, reduce_axis=reduce_axis,
                      reduce="ring" if use_ring else "psum")
    if spec.family == "pool2d":
        from repro.kernels.pool2d.ops import pool2d
        return pool2d(x, window=spec.knob("window", (2, 2)),
                      stride=spec.knob("stride"),
                      mode=spec.knob("mode", "max"),
                      ip=site.ip.name)
    if spec.family == "activation":
        from repro.kernels.activation.ops import activation
        return activation(x, kind=spec.knob("kind", "relu"),
                          ip=site.ip.name)
    # cnn_fused (gated by _check_chain)
    from repro.kernels.fused.ops import fused_cnn_block
    return fused_cnn_block(
        x, w, pool_window=spec.knob("window", (2, 2)),
        pool_stride=spec.knob("stride"), pool_mode=spec.knob("mode", "max"),
        activation=spec.knob("kind", "relu"), ip=site.ip.name)


def apply_plan_replicated(plan: NetworkPlan, x: jnp.ndarray,
                          weights: Optional[Dict[str, jnp.ndarray]] = None
                          ) -> jnp.ndarray:
    """The single-device reference walk: every site's planned member on
    the full tensors, no mesh.  ``weights`` maps conv/fused site name ->
    its weight tensor."""
    _check_chain(plan)
    weights = weights or {}
    cur = x
    for site in plan.sites:
        cur = _run_site(site, cur, weights.get(site.spec.name))
    return cur


def _slice_block(x: jnp.ndarray, dim: int, degree: int,
                 index) -> jnp.ndarray:
    block = x.shape[dim] // degree
    return jax.lax.dynamic_slice_in_dim(x, index * block, block, axis=dim)


def _relay(x: jnp.ndarray, have, want, axis: str, index) -> jnp.ndarray:
    """Move ``x`` from layout ``have`` to ``want`` inside shard_map.
    Layouts are ``core.shard`` tuples; a sharded source is gathered back
    to replicated first (the priced single-hop model), then slicing is
    free."""
    if have == want:
        return x
    if have != FULL:
        # tiled all-gather along the shard dim restores the global tensor
        dim = 0 if have[0] == "batch" else x.ndim - 1
        x = jax.lax.all_gather(x, axis, axis=dim, tiled=True)
    if want == FULL:
        return x
    dim = 0 if want[0] == "batch" else x.ndim - 1
    return _slice_block(x, dim, want[1], index)


def apply_plan_sharded(plan: NetworkPlan, x: jnp.ndarray,
                       weights: Optional[Dict[str, jnp.ndarray]] = None,
                       *, use_ring: bool = False,
                       devices=None) -> jnp.ndarray:
    """Execute ``plan`` under its mesh: one ``shard_map`` over the whole
    chain, layouts threaded exactly as the planner priced them.

    ``x`` and every weight enter replicated (``in_specs=P()``) and the
    result leaves replicated — identical contract to
    ``apply_plan_replicated``; a plan with no sharded sites (or no mesh)
    simply runs the replicated walk.  ``use_ring=True`` routes the
    channel-split conv's all-reduce through the explicit ppermute ring
    instead of ``lax.psum``.
    """
    _check_chain(plan)
    if (plan.mesh is None or plan.mesh.devices <= 1
            or not plan.sharded_sites()):
        return apply_plan_replicated(plan, x, weights)
    weights = weights or {}
    d = plan.mesh.devices
    axis = plan.mesh.axis
    devs = list(devices) if devices is not None else jax.devices()[:d]
    if len(devs) < d:
        raise ValueError(
            f"plan wants {d} devices but only {len(devs)} are available "
            "(set XLA_FLAGS=--xla_force_host_platform_device_count for "
            "host meshes)")
    mesh = Mesh(np.array(devs[:d]), (axis,))
    dplan = plan.device_plan()

    def device_fn(xg, wg):
        index = jax.lax.axis_index(axis)
        cur = xg
        have = FULL
        for gsite, dsite in zip(plan.sites, dplan.sites):
            need = required_input_layout(gsite.spec, gsite.shard_axis,
                                         gsite.shard_degree)
            cur = _relay(cur, have, need, axis, index)
            w = wg.get(gsite.spec.name)
            reduce_axis = None
            if (gsite.sharded and gsite.shard_axis == "chan"
                    and gsite.spec.family == "conv2d"):
                # weights split their input-channel dim with the data
                w = _slice_block(w, 2, gsite.shard_degree, index)
                reduce_axis = axis
            run = dsite if gsite.sharded else gsite
            cur = _run_site(run, cur, w, reduce_axis=reduce_axis,
                            use_ring=use_ring)
            have = output_layout(gsite.spec, gsite.shard_axis,
                                 gsite.shard_degree)
        return _relay(cur, have, FULL, axis, index)

    fn = shard_map(device_fn, mesh=mesh, in_specs=(P(), P()),
                   out_specs=P(), check_vma=False)
    with (TRACER.span("shard_exec.apply", devices=d, axis=axis,
                      comm_cycles=sum(s.footprint.comm_cycles
                                      for s in plan.sites))
          if TRACER.enabled else NOOP_SPAN):
        y = fn(x, dict(weights))
    if INJECTOR.enabled:
        # injection seam "collective": corruption lands on the gathered
        # result, after the collectives (inside shard_map is traced
        # code — a host-side perturbation there would be wrong anyway)
        y = INJECTOR.perturb_output("collective", y)
    return y
