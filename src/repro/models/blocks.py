"""Shared model blocks: norms, MLPs, embeddings, RoPE.

Functional style: ``init_*`` returns a param pytree (plain dicts of
jnp arrays), ``apply`` functions are pure.  Layer-stacked params carry a
leading group axis for lax.scan (see transformer.py).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------
def init_norm(cfg: ModelConfig, shape_prefix=()):
    pd = cfg.dtype("param")
    if cfg.norm == "layernorm_nonparam":
        return {}  # OLMo: no learnable scale/bias
    p = {"scale": jnp.ones(shape_prefix + (cfg.d_model,), pd)}
    if cfg.norm == "layernorm":
        p["bias"] = jnp.zeros(shape_prefix + (cfg.d_model,), pd)
    return p


def apply_norm(cfg: ModelConfig, p, x, eps: float = 1e-5):
    xf = x.astype(jnp.float32)
    if cfg.norm == "rmsnorm":
        y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
        return (y * p["scale"].astype(jnp.float32)).astype(x.dtype)
    mean = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    y = (xf - mean) * jax.lax.rsqrt(var + eps)
    if cfg.norm == "layernorm":
        y = y * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)
    return y.astype(x.dtype)


# ---------------------------------------------------------------------------
# Dense FFN
# ---------------------------------------------------------------------------
def init_ffn(cfg: ModelConfig, key, shape_prefix=(), d_in=None, d_ff=None):
    pd = cfg.dtype("param")
    d = d_in or cfg.d_model
    f = d_ff or cfg.d_ff
    k1, k2, k3 = jax.random.split(key, 3)
    scale = d ** -0.5
    if cfg.activation in ("swiglu", "geglu"):
        return {
            "w_gate": (jax.random.normal(k1, shape_prefix + (d, f)) * scale).astype(pd),
            "w_up": (jax.random.normal(k2, shape_prefix + (d, f)) * scale).astype(pd),
            "w_down": (jax.random.normal(k3, shape_prefix + (f, d)) * f ** -0.5).astype(pd),
        }
    return {
        "w_in": (jax.random.normal(k1, shape_prefix + (d, f)) * scale).astype(pd),
        "w_down": (jax.random.normal(k3, shape_prefix + (f, d)) * f ** -0.5).astype(pd),
    }


def apply_ffn(cfg: ModelConfig, p, x):
    cd = cfg.dtype("compute")
    x = x.astype(cd)
    if cfg.activation in ("swiglu", "geglu"):
        g = jnp.einsum("...d,df->...f", x, p["w_gate"].astype(cd))
        u = jnp.einsum("...d,df->...f", x, p["w_up"].astype(cd))
        act = jax.nn.silu if cfg.activation == "swiglu" else jax.nn.gelu
        h = act(g) * u
    else:
        h = jnp.einsum("...d,df->...f", x, p["w_in"].astype(cd))
        h = jax.nn.gelu(h) if cfg.activation == "gelu" else jnp.square(jax.nn.relu(h))
    return jnp.einsum("...f,fd->...d", h, p["w_down"].astype(cd))


# ---------------------------------------------------------------------------
# Embeddings / head
# ---------------------------------------------------------------------------
def init_embed(cfg: ModelConfig, key):
    pd = cfg.dtype("param")
    k1, k2 = jax.random.split(key)
    p = {"embed": (jax.random.normal(k1, (cfg.vocab_size, cfg.d_model))
                   * cfg.d_model ** -0.5).astype(pd)}
    if not cfg.tie_embeddings:
        p["lm_head"] = (jax.random.normal(k2, (cfg.d_model, cfg.vocab_size))
                        * cfg.d_model ** -0.5).astype(pd)
    return p


def embed_tokens(cfg: ModelConfig, p, tokens):
    return jnp.take(p["embed"], tokens, axis=0).astype(cfg.dtype("compute"))


def lm_logits(cfg: ModelConfig, p, x):
    cd = cfg.dtype("compute")
    w = (p["embed"].T if cfg.tie_embeddings else p["lm_head"]).astype(cd)
    return jnp.einsum("...d,dv->...v", x.astype(cd), w).astype(cfg.dtype("logit"))


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------
def rope_freqs(cfg: ModelConfig, positions):
    """positions: (...,) int32 -> cos/sin (..., rot_dim/2)."""
    rot = cfg.head_dim if cfg.rope_style == "full" else cfg.head_dim // 2
    inv = 1.0 / (cfg.rope_theta ** (jnp.arange(0, rot, 2, dtype=jnp.float32) / rot))
    ang = positions.astype(jnp.float32)[..., None] * inv
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(cfg: ModelConfig, x, cos, sin):
    """x: (B, S, H, Dh); cos/sin: (B, S, rot/2) or (S, rot/2)."""
    if cfg.rope_style == "none":
        return x
    rot = cfg.head_dim if cfg.rope_style == "full" else cfg.head_dim // 2
    xr, xp = x[..., :rot], x[..., rot:]
    x1 = xr[..., 0::2]
    x2 = xr[..., 1::2]
    while cos.ndim < x1.ndim:  # broadcast over head axis
        cos = cos[..., None, :]
        sin = sin[..., None, :]
    o1 = x1 * cos - x2 * sin
    o2 = x2 * cos + x1 * sin
    out = jnp.stack([o1, o2], axis=-1).reshape(xr.shape)
    return jnp.concatenate([out, xp], axis=-1) if rot < cfg.head_dim else out


# ---------------------------------------------------------------------------
# CNN block — conv -> pool -> activation, planned as one NetworkPlan: the
# three sites share ONE ResourceBudget *partitioned* across them (the
# paper's full-layer scenario: a CNN layer whose implementation adapts to
# available resources while its math stays fixed).
# ---------------------------------------------------------------------------
def init_cnn_block(key, cin: int, cout: int, k: int = 3,
                   dtype=jnp.float32):
    scale = (k * k * cin) ** -0.5
    return {"w": (jax.random.normal(key, (k, k, cin, cout)) * scale
                  ).astype(dtype)}


def cnn_block_site_specs(x_shape, w_shape, *, x_dtype, w_dtype=None,
                         pool_window=(2, 2), pool_stride=None,
                         pool_mode: str = "max", activation: str = "relu",
                         site: str = "cnn_block", ladder=()):
    """Declarative op sites of one conv -> pool -> act block.

    Intermediate shapes/dtypes come from the family oracles via
    ``jax.eval_shape`` (abstract, no FLOPs), so the specs always agree
    with what the kernels will actually produce.  Returns
    ``(specs, out_aval)`` — the latter lets a caller chain blocks into a
    single whole-network plan (see models/frontends.py).

    ``ladder`` (e.g. ``(16, 8)``) attaches the same precision ladder to
    all three sites: the planner may quantize any of them below native
    width when the budget demands it (docs/adaptive_ips.md, "Precision
    contract").
    """
    import functools

    from repro.core.ip import SiteSpec
    from repro.kernels.activation.ref import activation_ref
    from repro.kernels.conv2d.ref import conv2d_ref
    from repro.kernels.pool2d.ref import pool2d_ref

    x_aval = jax.ShapeDtypeStruct(tuple(x_shape), jnp.dtype(x_dtype))
    w_aval = jax.ShapeDtypeStruct(tuple(w_shape),
                                  jnp.dtype(w_dtype or x_dtype))
    conv_aval = jax.eval_shape(conv2d_ref, x_aval, w_aval)
    pool_aval = jax.eval_shape(
        functools.partial(pool2d_ref, window=pool_window, stride=pool_stride,
                          mode=pool_mode), conv_aval)
    act_aval = jax.eval_shape(
        functools.partial(activation_ref, kind=activation), pool_aval)
    specs = [
        SiteSpec.make(f"{site}.conv", "conv2d", (x_aval.shape, w_aval.shape),
                      x_aval.dtype, ladder=ladder, dual=False),
        SiteSpec.make(f"{site}.pool", "pool2d", (conv_aval.shape,),
                      conv_aval.dtype, ladder=ladder, window=pool_window,
                      stride=pool_stride, mode=pool_mode),
        SiteSpec.make(f"{site}.act", "activation", (pool_aval.shape,),
                      pool_aval.dtype, ladder=ladder, kind=activation),
    ]
    return specs, act_aval


def _apply_fused_site(fused_s, p, x, *, pool_window, pool_stride, pool_mode,
                      activation, plan, quant_report,
                      tile_overrides):
    """Execute one planned fused site: the whole conv -> pool -> act
    chain in a single launch.  The lowered rungs run the quantized fused
    kernel (int8: in-register rescale of the int32 accumulator);
    ``quant_report`` measures the one fused output against the composite
    family oracle."""
    if plan is not None:
        plan[fused_s.spec.name] = (fused_s.ip, fused_s.footprint)
    tile_kwargs = dict((tile_overrides or {}).get(fused_s.spec.name, {}))
    if fused_s.lowered:
        from repro.quant.ops import quantized_fused_cnn_block
        y = quantized_fused_cnn_block(
            x, p["w"], pool_window=pool_window, pool_stride=pool_stride,
            pool_mode=pool_mode, activation=activation,
            bits=fused_s.precision_bits, ip=fused_s.ip.name)
    else:
        from repro.kernels.fused.ops import fused_cnn_block
        y = fused_cnn_block(x, p["w"], pool_window=pool_window,
                            pool_stride=pool_stride, pool_mode=pool_mode,
                            activation=activation, ip=fused_s.ip.name,
                            **tile_kwargs)
    if quant_report is not None:
        from repro.core.library import get_family
        from repro.quant.report import record
        ref = get_family("cnn_fused").reference(
            x.astype(jnp.float32), p["w"].astype(jnp.float32),
            window=pool_window, stride=pool_stride, mode=pool_mode,
            kind=activation)
        record(quant_report, fused_s.spec.name, fused_s.precision_bits,
               y, ref)
    return y


def apply_cnn_block(p, x, *, budget=None, pool_window=(2, 2),
                    pool_stride=None, pool_mode: str = "max",
                    activation: str = "relu", plan=None,
                    site: str = "cnn_block", network=None,
                    ladder=(), quant_report=None, tile_overrides=None,
                    fuse: bool = True):
    """One adaptive CNN layer: conv -> pool -> activation.

    The three sites are planned as one ``NetworkPlan`` under a
    partitioned ``budget`` (memoized — re-tracing the same shapes hits
    the plan cache with zero new selector evaluations), then each stage
    runs its planned Pallas kernel.  Pass ``network`` (a NetworkPlan
    containing this block's sites, e.g. one spanning a whole frontend)
    to execute from an outer plan instead.  When ``plan`` (a dict) is
    passed, the three (KernelIP, Footprint) decisions are recorded
    under ``site`` — renderable with ``describe_plan``.

    **Fusion.** ``fuse`` (default True) plans with fusion-aware substitution
    (``core.plan.plan_network(..., fuse=True)``): when the planner maps
    this block onto a single fused site (``<site>.fused``), the whole
    conv -> pool -> activation chain executes as ONE ``pallas_call``
    with no intermediate HBM round-trips — including the lowered rungs,
    where the int8 kernel rescales its int32 accumulator in register.
    Execution is plan-driven: a supplied ``network`` containing
    ``<site>.fused`` runs fused regardless of ``fuse``, and the planner
    falls back to the three-site chain whenever the fused footprint
    does not fit (docs/adaptive_ips.md, "Fusion contract").

    **Mixed precision.** With a ``ladder`` the planner may assign any
    site a lowered operand width; execution honors the plan with
    quantize/dequantize boundaries inserted only where adjacent sites
    disagree: an int8 conv feeds its (requantized) codes straight into
    an int8 pool, and an int8 relu runs on the codes too (relu commutes
    with the positive scale), so a fully-lowered block performs ONE
    dequantize at its egress.  ``quant_report`` (a dict) receives a
    ``SiteQuantReport`` per site — the measured relative error vs the
    family oracles evaluated in float32.

    ``tile_overrides`` maps site name -> tiling kwargs for that site's
    kernel call (e.g. ``{"cnn_block.conv": {"block_cout": 256}}`` from
    ``core.autotune.plan_tile_overrides``); only full-precision sites
    honor them — the quantized wrappers keep their members' defaults.
    """
    from repro.core.plan import plan_network
    from repro.kernels.activation.ops import activation as activation_op
    from repro.kernels.conv2d.ops import conv2d
    from repro.kernels.pool2d.ops import pool2d

    specs, _ = cnn_block_site_specs(
        x.shape, p["w"].shape, x_dtype=x.dtype, w_dtype=p["w"].dtype,
        pool_window=pool_window, pool_stride=pool_stride,
        pool_mode=pool_mode, activation=activation, site=site,
        ladder=ladder)
    if network is None:
        network = plan_network(specs, budget, fuse=fuse)
    else:
        # An outer plan was built from its own view of the graph; its
        # feasibility guarantees are void if that view disagrees with
        # this call's actual shapes/dtypes/knobs.
        from repro.core.library import get_family
        fused_view = get_family("cnn_fused").fuse_sites(tuple(specs))
        if f"{site}.fused" in network and fused_view is None:
            raise ValueError(
                f"plan/site mismatch at '{site}.fused': the supplied "
                f"network fused this block, but this call's sites "
                f"{[s.name for s in specs]} are not fusable")
        check = ([fused_view] if f"{site}.fused" in network else specs)
        for spec in check:
            planned = network.site(spec.name).spec
            if planned != spec:
                raise ValueError(
                    f"plan/site mismatch at {spec.name!r}: the supplied "
                    f"network was planned for {planned}, but this call "
                    f"executes {spec}")

    if f"{site}.fused" in network:
        return _apply_fused_site(
            network.site(f"{site}.fused"), p, x, pool_window=pool_window,
            pool_stride=pool_stride, pool_mode=pool_mode,
            activation=activation, plan=plan,
            quant_report=quant_report, tile_overrides=tile_overrides)

    conv_s = network.site(f"{site}.conv")
    pool_s = network.site(f"{site}.pool")
    act_s = network.site(f"{site}.act")
    if plan is not None:
        for s in (conv_s, pool_s, act_s):
            plan[s.spec.name] = (s.ip, s.footprint)

    if quant_report is not None:
        import functools

        from repro.kernels.activation.ref import activation_ref
        from repro.kernels.conv2d.ref import conv2d_ref
        from repro.kernels.pool2d.ref import pool2d_ref
        from repro.quant.report import record
        ref = conv2d_ref(x.astype(jnp.float32),
                         p["w"].astype(jnp.float32))
        pool_ref = functools.partial(pool2d_ref, window=pool_window,
                                     stride=pool_stride, mode=pool_mode)

    # qscale is not None  <=>  y holds fixed-point codes (or an integer
    # accumulator) whose real value is y * qscale.
    qscale = None

    # -- conv ---------------------------------------------------------------
    if conv_s.lowered:
        from repro.quant.ops import quantized_conv2d
        # int8 returns the raw accumulator + scale (the dequantize fuses
        # into the next stage); 16-bit fake-quant returns (float, None).
        y, qscale = quantized_conv2d(x, p["w"], bits=conv_s.precision_bits,
                                     ip=conv_s.ip.name, return_scale=True)
    else:
        y = conv2d(x, p["w"], ip=conv_s.ip.name,
                   **dict((tile_overrides or {}).get(conv_s.spec.name, {})))
    if quant_report is not None:
        got = y if qscale is None else y.astype(jnp.float32) * qscale
        record(quant_report, conv_s.spec.name, conv_s.precision_bits,
               got, ref)

    # -- pool ---------------------------------------------------------------
    if qscale is not None and pool_s.precision_bits == 8 and pool_s.lowered:
        # Adjacent int8 sites: requantize the int32 accumulator to int8
        # codes (the standard fixed-point interlayer step) and pool the
        # codes — no float boundary.
        from repro.quant.quantize import quantize_acts
        yq = quantize_acts(y.astype(jnp.float32) * qscale, bits=8)
        y = pool2d(yq.q, window=pool_window, stride=pool_stride,
                   mode=pool_mode, ip=pool_s.ip.name)
        qscale = yq.scale
    else:
        if qscale is not None:  # widths disagree: dequantize boundary
            y = y.astype(jnp.float32) * qscale
            qscale = None
        if pool_s.lowered:
            from repro.quant.ops import quantized_pool2d
            y = quantized_pool2d(y, window=pool_window, stride=pool_stride,
                                 mode=pool_mode,
                                 bits=pool_s.precision_bits,
                                 ip=pool_s.ip.name)
        else:
            y = pool2d(y, window=pool_window, stride=pool_stride,
                       mode=pool_mode, ip=pool_s.ip.name)
    if quant_report is not None:
        ref = pool_ref(ref)
        got = y if qscale is None else y.astype(jnp.float32) * qscale
        record(quant_report, pool_s.spec.name, pool_s.precision_bits,
               got, ref)

    # -- activation ---------------------------------------------------------
    if (qscale is not None and act_s.lowered and activation == "relu"
            and act_s.precision_bits == pool_s.precision_bits):
        # relu(q * s) == relu(q) * s for s > 0: the activation runs on
        # the codes and the whole lowered chain dequantizes ONCE here.
        y = activation_op(y, kind="relu", ip=act_s.ip.name)
        y = y * qscale
        qscale = None
    else:
        if qscale is not None:
            y = y.astype(jnp.float32) * qscale
            qscale = None
        if act_s.lowered:
            from repro.quant.ops import quantized_activation
            y = quantized_activation(y, kind=activation,
                                     bits=act_s.precision_bits,
                                     ip=act_s.ip.name)
        else:
            y = activation_op(y, kind=activation, ip=act_s.ip.name)
    if quant_report is not None:
        from repro.kernels.activation.ref import activation_ref
        ref = activation_ref(ref, kind=activation)
        record(quant_report, act_s.spec.name, act_s.precision_bits, y, ref)
    return y


# ---------------------------------------------------------------------------
# Loss
# ---------------------------------------------------------------------------
def softmax_xent(logits, labels, *, z_loss: float = 1e-4):
    """Token-mean cross entropy (f32 accumulation) + z-loss regularizer."""
    lf = logits.astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(lf, axis=-1)
    ll = jnp.take_along_axis(lf, labels[..., None], axis=-1)[..., 0]
    loss = jnp.mean(lse - ll)
    if z_loss:
        loss = loss + z_loss * jnp.mean(jnp.square(lse))
    return loss
