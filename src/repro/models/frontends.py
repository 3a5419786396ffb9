"""Modality frontends + input spec providers.

Per the assignment, [audio]/[vlm] archs specify the transformer
backbone only: ``input_specs()`` provides precomputed frame/patch
embeddings.  The CNN vision frontend below is the exception — a real
adaptive-IP image stem (conv -> pool -> activation per block, all
selector-dispatched) that produces those patch embeddings itself.  This module is the single source of truth for what each
(arch x shape x step-kind) consumes — used identically by the dry-run
(abstract ShapeDtypeStructs) and by tests/examples (concrete sampled
arrays via ``make_inputs(..., abstract=False)``).
"""
from __future__ import annotations

from typing import Any, Dict

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
