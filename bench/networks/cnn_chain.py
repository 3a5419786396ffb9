"""The chain network: blocks of valid KxK conv (stride 1) -> non-overlapping
max pool -> activation, then a per-position projection to ``d_model``.

A configuration names it with ``"network": "cnn_chain"`` and gives
``image`` (H, W, C), ``channels`` (input, then one width per block),
``kernel``, ``pool_window``, ``activation`` (``relu`` or ``tanh``),
``d_model`` and ``dtype``.

The reference is the chain in plain ``jax.numpy`` and ``lax``, in
float32 with every product at ``Precision.HIGHEST``.  ``passes=3`` is the
control: the same network with every conv and projection product split
into bf16 halves and the low-by-low term dropped, which is what a
three-pass bf16 matmul (``Precision.HIGH``) computes.  It is built from
bf16 operands with f32 accumulation, so it reads the same on the CPU as
on the chip.  Only ``register`` and ``replace_served`` touch the program.

Work counts depend only on the shapes, not on which kernel member or
fusion runs a block:

- operations: 2 per conv multiply-add, ``window - 1`` compares per pooled
  output, 1 per activated output, 2 per projection multiply-add;
- minimum bytes: a block reads its input and weights once and writes its
  pooled, activated output once; the projection reads its input and
  weights once and writes its output once.  Intermediates that a fused
  kernel keeps on chip are not counted, so an unfused chain reads as
  further from its roofline than a fused one.
"""
from __future__ import annotations

import contextlib
import functools
from typing import List

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from bench.flops import Work
from bench.reference import _products, prng_key

ACTIVATIONS = {"relu": lambda x: jnp.maximum(x, 0.0), "tanh": jnp.tanh}


def make(config, seed: int, pool: int):
    """Weights and a pool of ``pool`` frames, made on the device in one
    jitted call.  Weights are normal, scaled by fan-in ** -0.5."""
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


# -- the reference ----------------------------------------------------------

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


# -- work counts ------------------------------------------------------------

def block_shapes(config) -> List[tuple]:
    """(h, w, cin, cout, k, ph, pw) of each block, chained by shape."""
    h, w, _ = config["image"]
    k = config["kernel"]
    ph, pw = config["pool_window"]
    out = []
    chans = config["channels"]
    for cin, cout in zip(chans[:-1], chans[1:]):
        out.append((h, w, cin, cout, k, ph, pw))
        h, w = (h - k + 1) // ph, (w - k + 1) // pw
    return out


def block_work(n, h, w, cin, cout, k, ph, pw, itemsize=4) -> Work:
    """One conv -> max pool -> activation block on a batch of ``n``."""
    ho, wo = h - k + 1, w - k + 1
    po, qo = ho // ph, wo // pw
    outs = n * po * qo * cout
    flops = (2.0 * n * ho * wo * cout * k * k * cin
             + outs * (ph * pw - 1) + outs)
    nbytes = itemsize * (n * h * w * cin + k * k * cin * cout + outs)
    return Work(flops, nbytes)


def conv_macs(config) -> float:
    """Multiply-adds of the convs for one frame."""
    return sum((h - k + 1) * (w - k + 1) * cout * k * k * cin
               for h, w, cin, cout, k, _, _ in block_shapes(config))


def final_positions(config):
    """(positions S, channels C) the projection sees."""
    h, w, cin, cout, k, ph, pw = block_shapes(config)[-1]
    return ((h - k + 1) // ph) * ((w - k + 1) // pw), cout


def projection_work(config, n, itemsize=4) -> Work:
    s, c = final_positions(config)
    d = config["d_model"]
    return Work(2.0 * n * s * c * d,
                itemsize * (n * s * c + c * d + n * s * d))


def blocks_work(config, n) -> Work:
    """Every block's kernel call on a batch of ``n``."""
    total = Work(0.0, 0.0)
    for w in calls(config, n):
        total = total + w
    return total


def calls(config, n) -> List[Work]:
    """The kernel calls of one launch of ``n`` frames that the roofline
    counts: one per block (the projection is not a Pallas kernel)."""
    return [block_work(n, *shape) for shape in block_shapes(config)]


def frame_flops(config) -> float:
    """Operations of the whole frontend for one frame, projection
    included."""
    return blocks_work(config, 1).flops + projection_work(config, 1).flops


# -- the program ------------------------------------------------------------

def register(sched, tenant: str, config, params, slo):
    """Register the chain with the SLO scheduler, the program's entry."""
    sched.register(tenant, params, tuple(config["image"]), slo=slo,
                   pool_window=tuple(config["pool_window"]),
                   activation=config["activation"])


@contextlib.contextmanager
def replace_served(wrap):
    """The program's served frontend ``f(params, images, **kwargs)``
    replaced by ``wrap(f)`` for every batch a server built inside the
    block launches."""
    from repro.runtime import server
    served = server.apply_cnn_frontend
    server.apply_cnn_frontend = wrap(served)
    try:
        yield
    finally:
        server.apply_cnn_frontend = served
