"""GPipe-style pipeline parallelism via shard_map + ppermute.

Each rank along the ``pipe`` mesh axis owns one stage's params; micro-
batches stream through the ring with a collective_permute handoff per
tick.  Fill+drain schedule: n_micro + n_stages - 1 ticks.  This is the
PP building block referenced in DESIGN.md §5 (usable across pods, where
the pod axis = stage axis and only point-to-point traffic crosses the
inter-pod links).
"""
from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P


def gpipe_forward(stage_fn: Callable, mesh: Mesh, *, axis: str = "pipe"):
    """Build a pipelined forward over one mesh axis.

    stage_fn(stage_params, x) -> y, applied by every rank to the
    microbatch currently resident on it.

    Returns pipelined(stage_params_stacked, x_micro) where
      stage_params_stacked: pytree with leading dim n_stages,
      x_micro: (n_micro, micro_batch, ...) input microbatches,
    and the result is (n_micro, micro_batch, ...) outputs of the LAST
    stage, in order.
    """
    n_stages = mesh.shape[axis]

    def per_rank(params_local, x_micro):
        # params_local: stage params with leading dim 1 (this rank's)
        params = jax.tree.map(lambda t: t[0], params_local)
        rank = jax.lax.axis_index(axis)
        n_micro = x_micro.shape[0]
        ticks = n_micro + n_stages - 1
        buf = jnp.zeros_like(x_micro[0])
        outs = jnp.zeros((n_micro,) + x_micro.shape[1:], x_micro.dtype)

        def tick(t, carry):
            buf, outs = carry
            # stage 0 ingests microbatch t during fill; drain ticks read
            # index 0 (any in-bounds index) and select zeros — never a
            # clamped re-read of the last microbatch
            ingesting = t < n_micro
            x_in = jnp.where(ingesting,
                             x_micro[jnp.where(ingesting, t, 0)],
                             jnp.zeros_like(buf))
            my_in = jnp.where(rank == 0, x_in, buf)
            y = stage_fn(params, my_in)
            # rank r's tick-t compute is microbatch (t - r): only the
            # fill+drain window [r, r + n_micro) is real work.  Mask the
            # stale ticks explicitly so whatever stage_fn makes of a
            # zero/garbage buffer (f(0) != 0, NaNs, ...) can never reach
            # the handoff or the emitted outputs.
            valid = jnp.logical_and(rank <= t, t - rank < n_micro)
            y = jnp.where(valid, y, jnp.zeros_like(y))
            # last stage emits microbatch (t - (n_stages-1)) at this tick
            out_idx = t - (n_stages - 1)
            emit = jnp.logical_and(rank == n_stages - 1, out_idx >= 0)
            outs = jax.lax.cond(
                emit,
                lambda o: o.at[jnp.maximum(out_idx, 0)].set(y),
                lambda o: o, outs)
            # hand off to the next stage
            buf = jax.lax.ppermute(
                y, axis, [(i, (i + 1) % n_stages) for i in range(n_stages)])
            return buf, outs

        _, outs = jax.lax.fori_loop(0, ticks, tick, (buf, outs))
        # outs only valid on the last rank; broadcast it ring-wise
        outs = jax.lax.ppermute(
            outs, axis, [(i, (i + 1) % n_stages) for i in range(n_stages)])
        # after one hop, rank 0 holds them; psum-select for simplicity
        outs = jax.lax.psum(
            jnp.where(rank == 0, outs, jnp.zeros_like(outs)), axis)
        return outs

    def wrapper(stage_params, x_micro):
        param_specs = jax.tree.map(lambda _: P(axis), stage_params)
        return shard_map(
            per_rank, mesh=mesh,
            in_specs=(param_specs, P()),
            out_specs=P(),
            check_vma=False)(stage_params, x_micro)

    return wrapper
