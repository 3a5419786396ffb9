"""The residual network: a bottleneck ResNet (He et al., arXiv:1512.03385,
Table 1), with the stride on each downsampling bottleneck's 3x3 conv
("v1.5").

A configuration names it with ``"network": "resnet"`` and gives
``image`` (H, W, C), ``stem`` (``channels``, ``kernel``, ``stride``,
``padding``), ``pool`` (``window``, ``stride``, ``padding``), the
bottleneck ``widths``, the ``blocks`` per stage, the ``expansion``, the
stage ``strides``, ``classes`` and ``dtype``.  Every conv carries a
per-channel bias: batch norm folded into the conv, exact at inference.
The first block of each stage has a 1x1 projection shortcut (strided
with the stage).  The head is a global average pool and an FC layer to
``classes`` logits, with no softmax.

The reference is the network in plain ``jax.numpy`` and ``lax``
(``conv_general_dilated`` with the published strides and padding, the
residual adds, a -inf padded 3x3/2 max pool, the pooled head), in
float32 with every product at ``Precision.HIGHEST``; ``passes=3`` is the
control, every conv and FC product in three bf16 passes
(``bench/reference.py``).  Only ``register`` and ``replace_served``
touch the program.

Work counts depend only on the shapes:

- operations: 2 per conv multiply-add, then 1 per output for the bias,
  1 for the residual add and 1 for the ReLU where a unit has them;
  ``window - 1`` compares per max-pooled output; in the head, 1 add per
  pooled input, 2 per FC multiply-add and 1 per logit for its bias;
- minimum bytes: a conv unit reads its input, weights, bias and (with
  one) its shortcut once and writes its output once; the pool reads its
  input and writes its output once.

``calls`` counts the Pallas kernel calls of one launch, in the order the
program's plan runs them: the stem's conv unit, the max pool, and per
bottleneck its conv units (c1, c2, the projection where there is one,
c3).  Every conv unit is one call of the program's ``res_conv`` member
(``res_conv_calls``).
"""
from __future__ import annotations

import contextlib
import functools
from typing import Iterator, List

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from bench.flops import Work
from bench.reference import _products, prng_key

# Scale of each bottleneck's last conv (c3: its folded batch norm) and of
# the projection shortcut, against He-normal: the 16 residual adds then
# keep the activations of order 1 through the network.
C3_SCALE = 0.2
PROJ_SCALE = 0.5
BIAS_STD = 0.1


def units(config) -> Iterator[tuple]:
    """Every conv unit in program order, chained by shape: (name, h, w,
    cin, cout, k, stride, padding, residual, relu) at an input of h x
    w."""
    h, w, cin = config["image"]
    st = config["stem"]
    yield ("stem", h, w, cin, st["channels"], st["kernel"], st["stride"],
           st["padding"], False, True)
    h, w = _out(h, st["kernel"], st["stride"], st["padding"]), \
        _out(w, st["kernel"], st["stride"], st["padding"])
    pool = config["pool"]
    h, w = _out(h, pool["window"], pool["stride"], pool["padding"]), \
        _out(w, pool["window"], pool["stride"], pool["padding"])
    cin = st["channels"]
    for si, (width, n, stride) in enumerate(zip(
            config["widths"], config["blocks"], config["strides"])):
        cout = width * config["expansion"]
        for bi in range(n):
            s = stride if bi == 0 else 1
            name = f"s{si}b{bi}"
            ho, wo = _out(h, 3, s, 1), _out(w, 3, s, 1)
            yield (f"{name}.c1", h, w, cin, width, 1, 1, 0, False, True)
            yield (f"{name}.c2", h, w, width, width, 3, s, 1, False, True)
            if bi == 0:
                yield (f"{name}.proj", h, w, cin, cout, 1, s, 0, False,
                       False)
            yield (f"{name}.c3", ho, wo, width, cout, 1, 1, 0, True, True)
            h, w, cin = ho, wo, cout


def _out(h, k, s, p):
    return (h + 2 * p - k) // s + 1


def make(config, seed: int, pool: int):
    """Weights and a pool of ``pool`` frames, made on the device in one
    jitted call.  Convs are He-normal (scaled by (2 / fan-in) ** 0.5),
    c3 by ``C3_SCALE`` and projections by ``PROJ_SCALE`` more; biases
    normal with std ``BIAS_STD``; the FC layer normal scaled by fan-in
    ** -0.5 with a zero bias."""
    specs = [(name, k, cin, cout) for name, _, _, cin, cout, k, *_ in
             units(config)]
    classes = config["classes"]
    feat = config["widths"][-1] * config["expansion"]
    h, w, c = config["image"]
    dtype = jnp.dtype(config["dtype"])

    @jax.jit
    def make(key):
        keys = jax.random.split(key, 2 * len(specs) + 2)
        convs = {}
        for i, (name, k, cin, cout) in enumerate(specs):
            scale = ((2.0 / (k * k * cin)) ** 0.5
                     * (C3_SCALE if name.endswith(".c3") else
                        PROJ_SCALE if name.endswith(".proj") else 1.0))
            convs[name] = {
                "w": (jax.random.normal(keys[2 * i], (k, k, cin, cout))
                      * scale).astype(dtype),
                "b": (BIAS_STD * jax.random.normal(keys[2 * i + 1], (cout,))
                      ).astype(dtype)}
        fc = {"w": (jax.random.normal(keys[-2], (feat, classes))
                    * feat ** -0.5).astype(dtype),
              "b": jnp.zeros((classes,), dtype)}
        frames = jax.random.normal(keys[-1], (pool, h, w, c), dtype)
        return _tree(config, convs, fc), frames

    return make(prng_key(seed, 0))


def _tree(config, convs, fc):
    """The weights as the program's residual frontend takes them:
    ``stem``, ``stages`` (a list of blocks, each with ``c1``, ``c2``,
    ``c3`` and, first in its stage, ``proj``) and ``fc``."""
    stages = []
    for si, n in enumerate(config["blocks"]):
        stages.append([{part: convs[f"s{si}b{bi}.{part}"]
                        for part in ("c1", "c2", "c3", "proj")
                        if f"s{si}b{bi}.{part}" in convs}
                       for bi in range(n)])
    return {"stem": convs["stem"], "stages": stages, "fc": fc}


# -- the reference ----------------------------------------------------------

def _conv_op(stride, padding):
    def op(x, w, precision):
        return lax.conv_general_dilated(
            x, w, window_strides=(stride, stride),
            padding=((padding, padding), (padding, padding)),
            dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=precision,
            preferred_element_type=jnp.float32)
    return op


def _fc(x, w, precision):
    return jnp.dot(x, w, precision=precision,
                   preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("layout", "passes"))
def _forward(params, x, *, layout, passes):
    stem, pool, strides = layout

    def unit(p, x, stride, padding, relu=True, residual=None):
        y = _products(_conv_op(stride, padding), x,
                      p["w"].astype(jnp.float32), passes) + p["b"]
        if residual is not None:
            y = y + residual
        return jnp.maximum(y, 0.0) if relu else y

    x = unit(params["stem"], x.astype(jnp.float32), stem[0], stem[1])
    window, stride, pad = pool
    x = lax.reduce_window(x, -jnp.inf, lax.max, (1, window, window, 1),
                          (1, stride, stride, 1),
                          ((0, 0), (pad, pad), (pad, pad), (0, 0)))
    for stage, s in zip(params["stages"], strides):
        for bi, bp in enumerate(stage):
            st = s if bi == 0 else 1
            y = unit(bp["c1"], x, 1, 0)
            y = unit(bp["c2"], y, st, 1)
            shortcut = (unit(bp["proj"], x, st, 0, relu=False)
                        if "proj" in bp else x)
            x = unit(bp["c3"], y, 1, 0, residual=shortcut)
    pooled = jnp.mean(x, axis=(1, 2))
    return _products(_fc, pooled, params["fc"]["w"].astype(jnp.float32),
                     passes) + params["fc"]["b"]


def _layout(config):
    st, pool = config["stem"], config["pool"]
    return ((st["stride"], st["padding"]),
            (pool["window"], pool["stride"], pool["padding"]),
            tuple(config["strides"]))


def batch(config, params, frames, *, passes: int = 6):
    """(B, classes) logits of one batch of frames, on the device."""
    return _forward(params, frames, layout=_layout(config), passes=passes)


def forward(config, params, frames, *, passes: int = 6, block: int = 8):
    """(N, classes) logits of ``frames``, ``block`` frames at a time, as
    a NumPy array."""
    return np.concatenate([
        np.asarray(batch(config, params, frames[i:i + block], passes=passes))
        for i in range(0, frames.shape[0], block)])


# -- work counts ------------------------------------------------------------

def unit_work(n, h, w, cin, cout, k, stride, padding, residual, relu,
              itemsize=4) -> Work:
    """One conv unit (conv, bias, optional add, optional ReLU) on a batch
    of ``n``."""
    ho, wo = _out(h, k, stride, padding), _out(w, k, stride, padding)
    outs = n * ho * wo * cout
    flops = (2.0 * outs * k * k * cin
             + outs * (1 + int(residual) + int(relu)))
    nbytes = itemsize * (n * h * w * cin + k * k * cin * cout + cout
                         + outs * (1 + int(residual)))
    return Work(flops, nbytes)


def pool_work(config, n, itemsize=4) -> Work:
    """The stem's max pool on a batch of ``n``."""
    h, w, _ = config["image"]
    st, pool = config["stem"], config["pool"]
    c = st["channels"]
    h = _out(h, st["kernel"], st["stride"], st["padding"])
    w = _out(w, st["kernel"], st["stride"], st["padding"])
    k, s, p = pool["window"], pool["stride"], pool["padding"]
    outs = n * _out(h, k, s, p) * _out(w, k, s, p) * c
    return Work(outs * (k * k - 1.0), itemsize * (n * h * w * c + outs))


def res_conv_calls(config, n) -> List[Work]:
    """The calls of one launch of ``n`` frames that the program's
    ``res_conv`` member serves: every conv unit."""
    return [unit_work(n, *u[1:]) for u in units(config)]


def calls(config, n) -> List[Work]:
    """Every Pallas kernel call of one launch of ``n`` frames, in plan
    order: the stem's unit, the max pool, then the bottlenecks' units
    (the head is not a Pallas kernel)."""
    res = res_conv_calls(config, n)
    return [res[0], pool_work(config, n)] + res[1:]


def head_flops(config) -> float:
    """The head of one frame: the average pool's adds, the FC layer's
    multiply-adds and its bias."""
    feat = config["widths"][-1] * config["expansion"]
    classes = config["classes"]
    _, h, w, *_ = list(units(config))[-1]      # c3 keeps its input's size
    return feat * h * w + 2.0 * feat * classes + classes


def frame_flops(config) -> float:
    """Operations of the whole network for one frame, head included."""
    return sum(w.flops for w in calls(config, 1)) + head_flops(config)


# -- the program ------------------------------------------------------------

def description(config):
    """The program's description of the network (``ResidualNet``): the
    stage strides and the stem's and its pool's geometry, as the
    configuration states them."""
    from repro.models.frontends import ResidualNet
    st, pool = config["stem"], config["pool"]
    return ResidualNet(
        strides=tuple(config["strides"]), stem_stride=st["stride"],
        stem_padding=st["padding"], pool_window=pool["window"],
        pool_stride=pool["stride"], pool_padding=pool["padding"])


def register(sched, tenant: str, config, params, slo):
    """Register the network with the SLO scheduler, the program's entry,
    under its ``description``."""
    sched.register(tenant, params, tuple(config["image"]), slo=slo,
                   net=description(config))


@contextlib.contextmanager
def replace_served(wrap):
    """The program's served residual frontend ``f(params, images,
    **kwargs)`` replaced by ``wrap(f)`` for every batch a server built
    inside the block launches."""
    from repro.runtime import server
    served = server.apply_resnet_frontend
    server.apply_resnet_frontend = wrap(served)
    try:
        yield
    finally:
        server.apply_resnet_frontend = served
