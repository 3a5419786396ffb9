"""What every network's plain reference shares.

Nothing here imports the program under test.  The benchmark makes the
weights and the frame pool itself, from the seed, and hands the same
arrays to the program and to the reference; each network's own
reference is in ``bench/networks/<network>.py``.  A reference computes
its products in float32 at ``Precision.HIGHEST`` (``passes=6``), and its
control as three bf16 passes (``passes=3``, what ``Precision.HIGH``
computes), through ``_products``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax


def prng_key(seed: int, stream: int):
    """A key from a seed of any size (JAX keeps 32 bits of an int)."""
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    key = jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)
    return jax.random.fold_in(key, stream)


def _split(a):
    """``a`` as ``hi + lo`` in bf16: ``hi`` keeps the top 16 bits of each
    f32 (a bit mask, since a compiler may fold an f32 -> bf16 -> f32
    round trip away and leave ``lo`` zero), ``lo`` rounds the rest."""
    bits = lax.bitcast_convert_type(a, jnp.uint32) & jnp.uint32(0xFFFF0000)
    hi = lax.bitcast_convert_type(bits, jnp.float32)
    return hi.astype(jnp.bfloat16), (a - hi).astype(jnp.bfloat16)


def _products(op, a, b, passes):
    """``op(a, b)`` in f32 at HIGHEST, or as three bf16 passes."""
    if passes == 6:
        return op(a, b, lax.Precision.HIGHEST)
    ah, al = _split(a)
    bh, bl = _split(b)
    return op(ah, bh, None) + op(ah, bl, None) + op(al, bh, None)


def rel_errors(served, ref) -> np.ndarray:
    """Per frame: ||served - ref|| / ||ref||, in float64."""
    served = np.asarray(served, np.float64)
    ref = np.asarray(ref, np.float64)
    axes = tuple(range(1, ref.ndim))
    return (np.sqrt(((served - ref) ** 2).sum(axis=axes))
            / np.sqrt((ref ** 2).sum(axis=axes)))
