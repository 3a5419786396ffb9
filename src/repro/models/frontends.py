"""Modality frontends + input spec providers.

Per the assignment, [audio]/[vlm] archs specify the transformer
backbone only: ``input_specs()`` provides precomputed frame/patch
embeddings.  The CNN vision frontend below is the exception — a real
adaptive-IP image stem (conv -> pool -> activation per block, all
selector-dispatched) that produces those patch embeddings itself, and
a residual network (a bottleneck ResNet) that produces class logits.
This module is the single source of truth for what each
(arch x shape x step-kind) consumes — used identically by the dry-run
(abstract ShapeDtypeStructs) and by tests/examples (concrete sampled
arrays via ``make_inputs(..., abstract=False)``).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig, ShapeConfig


def _spec(shape, dtype):
    return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype))


def input_specs(cfg: ModelConfig, shape: ShapeConfig) -> Dict[str, Any]:
    """Abstract inputs for the step implied by shape.kind."""
    B, S = shape.global_batch, shape.seq_len
    cd = cfg.dtype("compute")
    if shape.kind == "train":
        if cfg.family == "encdec":
            return {"embeds": _spec((B, S, cfg.d_model), cd),
                    "tokens": _spec((B, S), jnp.int32),
                    "labels": _spec((B, S), jnp.int32)}
        if cfg.embed_inputs:
            return {"embeds": _spec((B, S, cfg.d_model), cd),
                    "labels": _spec((B, S), jnp.int32)}
        return {"tokens": _spec((B, S), jnp.int32),
                "labels": _spec((B, S), jnp.int32)}
    if shape.kind == "prefill":
        if cfg.family == "encdec":
            return {"embeds": _spec((B, S, cfg.d_model), cd),
                    "tokens": _spec((B, S), jnp.int32)}
        if cfg.embed_inputs:
            return {"embeds": _spec((B, S, cfg.d_model), cd)}
        return {"tokens": _spec((B, S), jnp.int32)}
    # decode: one new token against a cache of S (caches built separately)
    return {"tokens": _spec((B, 1), jnp.int32)}


# ---------------------------------------------------------------------------
# CNN vision frontend — a real (non-stub) image stem built from adaptive
# cnn_blocks: every conv/pool/activation inside is dispatched through the
# resource-driven selector, and the pooled feature map is flattened to the
# (B, S, d_model) patch-embedding contract `embed_inputs` models consume.
# ---------------------------------------------------------------------------
def init_cnn_frontend(key, *, channels=(3, 16, 32), k: int = 3,
                      d_model: int = 64, dtype=jnp.float32):
    from repro.models.blocks import init_cnn_block
    keys = jax.random.split(key, len(channels))
    blocks = [init_cnn_block(kb, cin, cout, k, dtype=dtype)
              for kb, cin, cout in zip(keys, channels[:-1], channels[1:])]
    proj = (jax.random.normal(keys[-1], (channels[-1], d_model))
            * channels[-1] ** -0.5).astype(dtype)
    return {"blocks": blocks, "proj": proj}


def cnn_frontend_site_specs(p, image_shape, image_dtype, *,
                            pool_window=(2, 2), activation: str = "relu",
                            ladder=()):
    """All op sites of the frontend stack, chained by abstract shapes —
    the whole-network graph the planner partitions one budget across.
    ``ladder`` attaches the same precision ladder to every site."""
    from repro.models.blocks import cnn_block_site_specs
    specs = []
    shape, dtype = tuple(image_shape), image_dtype
    for li, bp in enumerate(p["blocks"]):
        block_specs, out_aval = cnn_block_site_specs(
            shape, bp["w"].shape, x_dtype=dtype, w_dtype=bp["w"].dtype,
            pool_window=pool_window, activation=activation,
            site=f"frontend.block{li}", ladder=ladder)
        specs.extend(block_specs)
        shape, dtype = out_aval.shape, out_aval.dtype
    return specs


def apply_cnn_frontend(p, images, *, budget=None, pool_window=(2, 2),
                       activation: str = "relu", plan=None, ladder=(),
                       quant_report=None,
                       network=None, tile_overrides=None,
                       fuse: bool = True):
    """images: (B, H, W, Cin) -> patch embeddings (B, S, d_model).

    The entire stack (every conv/pool/act of every block) is planned as
    ONE NetworkPlan: the budget is partitioned across all sites at once
    rather than each block competing for the full envelope.  With a
    ``ladder`` the plan may be mixed-precision; each block executes its
    planned widths (see ``apply_cnn_block``) and ``quant_report``
    collects the per-site measured error across the whole stack.

    ``network`` executes from an externally built/arbitrated plan
    instead of planning here (the serving runtime's entry point —
    it re-plans tenants under moving budget slices via
    ``core.plan.replan`` and hands the result in); every block still
    validates its sites against the supplied plan.  ``tile_overrides``
    threads per-site tiling kwargs down to the kernels
    (``core.autotune.plan_tile_overrides``).

    NOTE the lowered blocks dequantize at their egress, so the ladder
    never changes this function's output dtype — only its accuracy,
    which the report quantifies.

    ``fuse`` (default True) plans the stack fusion-aware: every block
    the planner can map onto a fused conv->pool->act site executes as
    ONE launch (see ``apply_cnn_block``); blocks whose fused footprint
    does not fit keep the three-launch chain.  ``fuse=False`` opts out.
    """
    from repro.core.plan import plan_network
    from repro.models.blocks import apply_cnn_block
    if network is None:
        network = plan_network(
            cnn_frontend_site_specs(p, images.shape, images.dtype,
                                    pool_window=pool_window,
                                    activation=activation, ladder=ladder),
            budget, fuse=fuse)
    x = images
    for li, bp in enumerate(p["blocks"]):
        x = apply_cnn_block(bp, x, pool_window=pool_window,
                            activation=activation, plan=plan,
                            site=f"frontend.block{li}",
                            network=network, ladder=ladder,
                            quant_report=quant_report,
                            tile_overrides=tile_overrides)
    b, h, w, c = x.shape
    tokens = x.reshape(b, h * w, c)
    return jnp.einsum("bsc,cd->bsd", tokens, p["proj"].astype(x.dtype),
                      precision=jax.lax.Precision.HIGHEST)


# ---------------------------------------------------------------------------
# Network descriptions — what a serving tenant registers: the site specs
# of its graph at a batch shape, and the keyword arguments its frontend
# runs a plan with.  ``ChainNet`` is the chain above; ``ResidualNet`` the
# residual graph below.
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class ChainNet:
    """conv -> pool -> activation blocks, then a per-position projection
    (``apply_cnn_frontend``)."""

    pool_window: Tuple[int, int] = (2, 2)
    activation: str = "relu"
    kind = "chain"

    def site_specs(self, params, batch_shape, dtype, ladder=()):
        return tuple(cnn_frontend_site_specs(
            params, batch_shape, dtype, pool_window=self.pool_window,
            activation=self.activation, ladder=tuple(ladder)))

    def apply_kwargs(self) -> dict:
        return {"pool_window": self.pool_window,
                "activation": self.activation}

    def to_dict(self) -> dict:
        return {"kind": self.kind, "pool_window": list(self.pool_window),
                "activation": self.activation}


@dataclasses.dataclass(frozen=True)
class ResidualNet:
    """A bottleneck residual network (``apply_resnet_frontend``); the
    widths and block counts come from its weights.  ``strides`` gives
    each stage's stride; the stem conv's stride and zero padding and the
    stem max pool's square window, stride and padding are the rest of
    its geometry (ResNet-50's by default)."""

    strides: Tuple[int, ...] = (1, 2, 2, 2)
    stem_stride: int = 2
    stem_padding: int = 3
    pool_window: int = 3
    pool_stride: int = 2
    pool_padding: int = 1
    kind = "residual"

    def site_specs(self, params, batch_shape, dtype, ladder=()):
        if ladder:
            raise ValueError("a residual network runs at its native width "
                             "only: its res_conv and add sites have no "
                             "quantized path, so it takes no ladder")
        return tuple(resnet_site_specs(params, batch_shape, dtype, net=self))

    def apply_kwargs(self) -> dict:
        return {"net": self}

    def to_dict(self) -> dict:
        return {"kind": self.kind, **dataclasses.asdict(self),
                "strides": list(self.strides)}


def network_from_dict(d: dict):
    """The description ``to_dict`` wrote."""
    if d["kind"] == ChainNet.kind:
        return ChainNet(tuple(d["pool_window"]), d["activation"])
    if d["kind"] == ResidualNet.kind:
        geometry = {k: v for k, v in d.items() if k != "kind"}
        return ResidualNet(**{**geometry,
                              "strides": tuple(geometry["strides"])})
    raise ValueError(f"unknown network kind {d['kind']!r}")


# ---------------------------------------------------------------------------
# Residual frontend — a bottleneck ResNet (He et al., arXiv:1512.03385,
# Table 1; stride on each downsampling block's 3x3 conv, "v1.5").  Each
# conv carries a per-channel bias (a folded batch norm).  Sites, in
# program order:
#
#   stem.conv (7x7/2, pad 3)  stem.act  stem.pool (3x3/2, pad 1)
#   per block: c1.conv c1.act  c2.conv c2.act  [proj.conv]
#              c3.conv c3.add c3.act
#
# so the planner's fusion pass finds (conv, act) and (conv, add, act)
# runs, and a lone projection conv, for the ``res_conv`` member.  The
# global average pool and the FC head run in XLA after the last site.
#
# Every conv site is pinned to the MXU-style members (knob
# ``style="mxu"``).  The cost model prices the VPU member's
# broadcast-multiply-reduce body at the VPU's peak op rate, and an MXU
# dot of a short row as a whole 128-row pass, so it ranks the VPU member
# first at most ResNet-50 convs; planned that way, unfused, the network
# needs 1.9x the VMEM envelope and does not plan at all.  Timed alone on
# a v5e, the VPU member takes 8-31x its est cycles at ResNet-50's conv
# shapes and the MXU members 0.2-3.4x theirs (PERF.md, section 6).
# Re-pricing the VPU member alone does not plan the network (it still
# fails at 8-13x, and leaves the stem unfused at 16x); re-pricing both
# members moves the chain configurations' plans too.  So the pin stays
# until the cost model is fit on the chip (ROADMAP S5).
# ---------------------------------------------------------------------------


def _conv_site(name, x_shape, w_shape, dtype, stride, padding):
    from repro.core.ip import SiteSpec
    from repro.kernels.conv2d.inner import conv_out_hw
    n, h, w, _ = x_shape
    kh, kw, _, cout = w_shape
    spec = SiteSpec.make(f"{name}.conv", "conv2d", (x_shape, w_shape), dtype,
                         dual=False, stride=stride, padding=padding,
                         bias=True, style="mxu")
    return spec, (n, *conv_out_hw(h, w, kh, kw, stride, padding), cout)


def _act_site(name, shape, dtype):
    from repro.core.ip import SiteSpec
    return SiteSpec.make(f"{name}.act", "activation", (shape,), dtype,
                         kind="relu")


def _blocks(params, strides):
    """(site prefix, block params, stride of its 3x3) of every
    bottleneck."""
    for si, (stage, stride) in enumerate(zip(params["stages"], strides)):
        for bi, bp in enumerate(stage):
            yield f"s{si}b{bi}", bp, stride if bi == 0 else 1


def resnet_site_specs(p, image_shape, image_dtype, *,
                      net: ResidualNet = ResidualNet()):
    """Every op site of the residual network ``net`` at one batch
    shape."""
    from repro.core.ip import SiteSpec
    dtype = jnp.dtype(image_dtype)
    conv, shape = _conv_site("stem", tuple(image_shape),
                             p["stem"]["w"].shape, dtype, net.stem_stride,
                             net.stem_padding)
    k, st, pad = net.pool_window, net.pool_stride, net.pool_padding
    specs = [conv, _act_site("stem", shape, dtype),
             SiteSpec.make("stem.pool", "pool2d", (shape,), dtype,
                           window=(k, k), stride=(st, st), mode="max",
                           padding=pad)]
    n, h, w_, c = shape
    shape = (n, (h + 2 * pad - k) // st + 1, (w_ + 2 * pad - k) // st + 1, c)
    for name, bp, stride in _blocks(p, net.strides):
        x_shape = shape
        c1, shape = _conv_site(f"{name}.c1", x_shape, bp["c1"]["w"].shape,
                               dtype, 1, 0)
        k = bp["c2"]["w"].shape[0]
        c2, shape2 = _conv_site(f"{name}.c2", shape, bp["c2"]["w"].shape,
                                dtype, stride, (k - 1) // 2)
        specs += [c1, _act_site(f"{name}.c1", shape, dtype),
                  c2, _act_site(f"{name}.c2", shape2, dtype)]
        if "proj" in bp:
            proj, _ = _conv_site(f"{name}.proj", x_shape,
                                 bp["proj"]["w"].shape, dtype, stride, 0)
            specs.append(proj)
        c3, shape = _conv_site(f"{name}.c3", shape2, bp["c3"]["w"].shape,
                               dtype, 1, 0)
        specs += [c3,
                  SiteSpec.make(f"{name}.c3.add", "add", (shape, shape),
                                dtype),
                  _act_site(f"{name}.c3", shape, dtype)]
    return specs


def _run_conv(network, name, cp, x, *, stride, padding, relu,
              residual=None, tile_overrides=None):
    """One conv unit on its planned sites: the ``res_conv`` member when
    the plan fused it, else the conv member, the bias in XLA, the add
    member and the activation member."""
    from repro.kernels.activation.ops import activation
    from repro.kernels.conv2d.ops import conv2d
    if f"{name}.fused" in network:
        site = network.site(f"{name}.fused")
        return site.ip.impl(x, cp["w"], cp["b"], residual, stride=stride,
                            padding=padding, relu=relu,
                            **dict((tile_overrides or {}).get(
                                site.spec.name, {})))
    site = network.site(f"{name}.conv")
    y = conv2d(x, cp["w"], ip=site.ip.name, stride=stride, padding=padding,
               **dict((tile_overrides or {}).get(site.spec.name, {})))
    y = y + cp["b"]
    if residual is not None:
        y = network.site(f"{name}.add").ip.impl(y, residual)
    if relu:
        y = activation(y, kind="relu",
                       ip=network.site(f"{name}.act").ip.name)
    return y


def apply_resnet_frontend(p, images, *, net: ResidualNet = ResidualNet(),
                          budget=None, network=None, ladder=(), quant_report=None,
                          tile_overrides=None, fuse: bool = True):
    """images: (B, H, W, Cin) -> logits (B, classes).

    The whole graph is planned as ONE NetworkPlan (``network`` executes
    an externally built plan instead, as the serving runtime does).
    Each conv unit runs on its planned sites (``_run_conv``); the stem's
    max pool pads with zeros, which equals padding with -inf on its
    non-negative (ReLU) input.  The global average pool and the FC head
    run in XLA at ``Precision.HIGHEST``."""
    from repro.core.plan import plan_network
    from repro.kernels.pool2d.ops import pool2d
    if ladder or quant_report is not None:
        raise ValueError("a residual network runs at its native width only")
    specs = resnet_site_specs(p, images.shape, images.dtype, net=net)
    if network is None:
        network = plan_network(specs, budget, fuse=fuse)
    x = _run_conv(network, "stem", p["stem"], images, stride=net.stem_stride,
                  padding=net.stem_padding, relu=True,
                  tile_overrides=tile_overrides)
    pad = net.pool_padding
    x = jnp.pad(x, ((0, 0), (pad, pad), (pad, pad), (0, 0)))
    x = pool2d(x, window=(net.pool_window,) * 2,
               stride=(net.pool_stride,) * 2, mode="max",
               ip=network.site("stem.pool").ip.name)
    for name, bp, stride in _blocks(p, net.strides):
        k = bp["c2"]["w"].shape[0]
        y = _run_conv(network, f"{name}.c1", bp["c1"], x, stride=1,
                      padding=0, relu=True, tile_overrides=tile_overrides)
        y = _run_conv(network, f"{name}.c2", bp["c2"], y, stride=stride,
                      padding=(k - 1) // 2, relu=True,
                      tile_overrides=tile_overrides)
        shortcut = x
        if "proj" in bp:
            shortcut = _run_conv(network, f"{name}.proj", bp["proj"], x,
                                 stride=stride, padding=0, relu=False,
                                 tile_overrides=tile_overrides)
        x = _run_conv(network, f"{name}.c3", bp["c3"], y, stride=1,
                      padding=0, relu=True, residual=shortcut,
                      tile_overrides=tile_overrides)
    pooled = jnp.mean(x, axis=(1, 2))
    return jnp.dot(pooled, p["fc"]["w"].astype(pooled.dtype),
                   precision=jax.lax.Precision.HIGHEST) + p["fc"]["b"]


def init_resnet(key, *, stem: int = 64, widths=(64, 128, 256, 512),
                blocks=(3, 4, 6, 3), expansion: int = 4, in_ch: int = 3,
                classes: int = 1000, dtype=jnp.float32):
    """Random bottleneck ResNet weights (He-normal convs, the last conv
    of each bottleneck scaled by 0.2, small biases), shaped for
    ``apply_resnet_frontend``: ``widths`` are the bottleneck widths,
    ``expansion`` times them the stage outputs.  Made in one jitted
    call."""
    return jax.jit(functools.partial(
        _init_resnet, stem=stem, widths=tuple(widths), blocks=tuple(blocks),
        expansion=expansion, in_ch=in_ch, classes=classes,
        dtype=dtype))(key)


def _init_resnet(key, *, stem, widths, blocks, expansion, in_ch, classes,
                 dtype):
    keys = iter(jax.random.split(key, 4 * sum(blocks) + 3))

    def conv(k, cin, cout, scale=1.0):
        kw, kb = jax.random.split(next(keys))
        return {"w": (jax.random.normal(kw, (k, k, cin, cout))
                      * scale * (2.0 / (k * k * cin)) ** 0.5).astype(dtype),
                "b": (0.1 * jax.random.normal(kb, (cout,))).astype(dtype)}

    p = {"stem": conv(7, in_ch, stem), "stages": []}
    cin = stem
    for width, n in zip(widths, blocks):
        stage = []
        for bi in range(n):
            bp = {"c1": conv(1, cin, width), "c2": conv(3, width, width),
                  "c3": conv(1, width, width * expansion, 0.2)}
            if bi == 0:
                bp["proj"] = conv(1, cin, width * expansion, 0.5)
            stage.append(bp)
            cin = width * expansion
        p["stages"].append(stage)
    kw = next(keys)
    p["fc"] = {"w": (jax.random.normal(kw, (cin, classes))
                     * cin ** -0.5).astype(dtype),
               "b": jnp.zeros((classes,), dtype)}
    return p


def make_inputs(cfg: ModelConfig, shape: ShapeConfig, *, seed: int = 0,
                abstract: bool = True) -> Dict[str, Any]:
    specs = input_specs(cfg, shape)
    if abstract:
        return specs
    rng = np.random.default_rng(seed)
    out = {}
    for name, s in specs.items():
        if jnp.issubdtype(s.dtype, jnp.integer):
            out[name] = jnp.asarray(
                rng.integers(0, cfg.vocab_size, s.shape, dtype=np.int32))
        else:
            out[name] = jnp.asarray(
                rng.normal(0, 1, s.shape).astype(np.float32)).astype(s.dtype)
    return out
