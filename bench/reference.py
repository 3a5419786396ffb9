"""Weights, frames and the plain reference of the CNN frontend.

Nothing here imports the program under test.  The benchmark makes the
weights and the frame pool itself, from the seed, and hands the same
arrays to the program and to the reference.

The reference is the frontend in plain ``jax.numpy`` and ``lax``:
valid conv (stride 1) -> max pool -> activation per block, then the
per-position projection, all in float32 with every product at
``Precision.HIGHEST``.  ``passes=3`` is the control: the same network
with every conv and projection product split into bf16 halves and the
low-by-low term dropped, which is what a three-pass bf16 matmul
(``Precision.HIGH``) computes.  It is built from bf16 operands with f32
accumulation, so it reads the same on the CPU as on the chip.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

ACTIVATIONS = {"relu": lambda x: jnp.maximum(x, 0.0), "tanh": jnp.tanh}


def prng_key(seed: int, stream: int):
    """A key from a seed of any size (JAX keeps 32 bits of an int)."""
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    key = jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)
    return jax.random.fold_in(key, stream)


def make_weights_and_frames(config, seed: int, pool: int):
    """Frontend weights and a pool of ``pool`` frames, made on the device
    in one jitted call.  Weights are normal, scaled by fan-in ** -0.5."""
    chans = config["channels"]
    k = config["kernel"]
    d = config["d_model"]
    h, w, c = config["image"]
    dtype = jnp.dtype(config["dtype"])

    @jax.jit
    def make(key):
        keys = jax.random.split(key, len(chans) + 1)
        blocks = [{"w": (jax.random.normal(kb, (k, k, cin, cout))
                         * (k * k * cin) ** -0.5).astype(dtype)}
                  for kb, cin, cout in zip(keys, chans[:-1], chans[1:])]
        proj = (jax.random.normal(keys[-2], (chans[-1], d))
                * chans[-1] ** -0.5).astype(dtype)
        frames = jax.random.normal(keys[-1], (pool, h, w, c), dtype)
        return {"blocks": blocks, "proj": proj}, frames

    return make(prng_key(seed, 0))


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


def _conv(x, w, precision):
    return lax.conv_general_dilated(
        x, w, window_strides=(1, 1), padding="VALID",
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=precision,
        preferred_element_type=jnp.float32)


def _proj(t, p, precision):
    return jnp.einsum("bsc,cd->bsd", t, p, precision=precision,
                      preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("window", "activation",
                                             "passes"))
def _forward(params, x, *, window, activation, passes):
    act = ACTIVATIONS[activation]
    for bp in params["blocks"]:
        y = _products(_conv, x.astype(jnp.float32),
                      bp["w"].astype(jnp.float32), passes)
        y = lax.reduce_window(y, -jnp.inf, lax.max, (1, *window, 1),
                              (1, *window, 1), "VALID")
        x = act(y)
    b, h, w, c = x.shape
    return _products(_proj, x.reshape(b, h * w, c),
                     params["proj"].astype(jnp.float32), passes)


def batch(config, params, frames, *, passes: int = 6):
    """(B, S, d_model) outputs of one batch of frames, on the device."""
    return _forward(params, frames, window=tuple(config["pool_window"]),
                    activation=config["activation"], passes=passes)


def forward(config, params, frames, *, passes: int = 6, block: int = 8):
    """(N, S, d_model) outputs of ``frames``, ``block`` frames at a time
    so that the largest stage fits beside what the device holds."""
    return np.concatenate([
        np.asarray(batch(config, params, frames[i:i + block], passes=passes))
        for i in range(0, frames.shape[0], block)])


def rel_errors(served, ref) -> np.ndarray:
    """Per frame: ||served - ref|| / ||ref||, in float64."""
    served = np.asarray(served, np.float64)
    ref = np.asarray(ref, np.float64)
    axes = tuple(range(1, ref.ndim))
    return (np.sqrt(((served - ref) ** 2).sum(axis=axes))
            / np.sqrt((ref ** 2).sum(axis=axes)))
