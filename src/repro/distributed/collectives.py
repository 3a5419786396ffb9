"""Manual collectives for overlap experiments: bucketed gradient
all-reduce and a bidirectional-ring all-reduce built on ppermute.

pjit/XLA already schedules collectives asynchronously; these exist for
(a) the §Perf overlap hillclimb — issuing the grad all-reduce per
bucket *inside* the backward scan so communication overlaps remaining
compute, and (b) explicit cross-pod control (compression hooks attach
here).  All run under shard_map.
"""
from __future__ import annotations

import functools
from typing import Any, Sequence

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def ring_all_reduce(x: jnp.ndarray, axis_name: str) -> jnp.ndarray:
    """Bidirectional-ring all-reduce via ppermute (reduce-scatter +
    all-gather decomposition), equivalent to lax.psum.

    Exists to make the ring schedule explicit/controllable (chunked
    issue = overlap window); tests assert equality with psum.
    """
    # jax.lax.axis_size doesn't exist on jax<=0.4.x; psum of a literal 1
    # folds to the (static) axis size on every version.
    n = jax.lax.psum(1, axis_name)
    if n == 1:
        return x
    idx = jax.lax.axis_index(axis_name)
    orig_shape = x.shape
    pad = (-x.size) % n
    flat = jnp.pad(x.reshape(-1), (0, pad)).reshape(n, -1)

    # reduce-scatter: after n-1 hops, chunk (idx+1)%n holds the full sum
    def rs_step(i, acc_flat):
        send_chunk = (idx - i) % n
        piece = jax.lax.dynamic_index_in_dim(acc_flat, send_chunk, 0,
                                             keepdims=False)
        recv = jax.lax.ppermute(piece, axis_name,
                                [(j, (j + 1) % n) for j in range(n)])
        tgt = (idx - i - 1) % n
        return acc_flat.at[tgt].add(recv)

    flat = jax.lax.fori_loop(0, n - 1, rs_step, flat)

    # all-gather: rank j owns fully-reduced chunk (j+1)%n; circulate the
    # owned chunk around the ring n-1 times.
    def ag_step(i, acc_flat):
        src_chunk = (idx + 1 - i) % n
        piece = jax.lax.dynamic_index_in_dim(acc_flat, src_chunk, 0,
                                             keepdims=False)
        recv = jax.lax.ppermute(piece, axis_name,
                                [(j, (j + 1) % n) for j in range(n)])
        tgt = (idx - i) % n
        return acc_flat.at[tgt].set(recv)

    flat = jax.lax.fori_loop(0, n - 1, ag_step, flat)
    out = flat.reshape(-1)
    if pad:
        out = out[:-pad]
    return out.reshape(orig_shape)


def bucketed_psum(grads: Any, axis_name: str, *, n_buckets: int = 4):
    """All-reduce a grad pytree in ``n_buckets`` independent psums so
    XLA can overlap them with surrounding compute (vs one fused
    all-reduce at the end of backward)."""
    leaves, treedef = jax.tree_util.tree_flatten(grads)
    order = sorted(range(len(leaves)), key=lambda i: -leaves[i].size)
    buckets = [[] for _ in range(n_buckets)]
    sizes = [0] * n_buckets
    for i in order:  # greedy balance
        b = sizes.index(min(sizes))
        buckets[b].append(i)
        sizes[b] += leaves[i].size
    out = [None] * len(leaves)
    for idxs in buckets:
        if not idxs:
            continue
        reduced = jax.lax.psum(tuple(leaves[i] for i in idxs), axis_name)
        for i, r in zip(idxs, reduced):
            out[i] = r
    return jax.tree_util.tree_unflatten(treedef, out)
