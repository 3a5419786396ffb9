"""The adaptive IP library — paper Table I, machine-readable.

Families: conv2d is the paper's literal object; pool2d and activation
close its stated future work ("expand the library to include pooling
and activation functions"); matmul, attention, and ssm_scan are its
generalization to the assigned LM architectures.  Every member carries
the Table I capability bits and a footprint function pricing it against
the TPU resource vector.  The registration contract is documented in
docs/adaptive_ips.md.
"""
from __future__ import annotations

from repro.core.ip import IPFamily, KernelIP
from repro.kernels.conv2d import ip1_vpu, ip2_mxu, ip3_packed, ip4_dual
from repro.kernels.conv2d.ref import conv2d_ref
from repro.kernels.pool2d import mxu_im2col as pool_im2col_mod
from repro.kernels.pool2d import vpu_window as pool_vpu_mod
from repro.kernels.pool2d.ref import pool2d_ref
from repro.kernels.activation import lut_poly as act_lut_mod
from repro.kernels.activation import vpu_exact as act_exact_mod
from repro.kernels.activation.ref import activation_ref
from repro.kernels.matmul import dual as mm_dual
from repro.kernels.matmul import mxu as mm_mxu_mod
from repro.kernels.matmul.ref import matmul_ref
from repro.kernels.attention import decode as attn_decode_mod
from repro.kernels.attention import flash as attn_flash_mod
from repro.kernels.attention.ref import attention_ref

# --------------------------------------------------------------------------
# conv2d family — the paper's four IPs.
# --------------------------------------------------------------------------
CONV2D = IPFamily("conv2d", reference=conv2d_ref)
CONV2D.register(KernelIP(
    name="conv2d.ip1_vpu", family="conv2d", impl=ip1_vpu.conv2d_ip1,
    footprint_fn=ip1_vpu.footprint, uses_mxu=False, max_operand_bits=32,
    outputs_per_pass=1, tags=("paper:Conv1", "logic-only"),
    description="No DSP/MXU; one convolution per pass; high vector logic."))
CONV2D.register(KernelIP(
    name="conv2d.ip2_mxu", family="conv2d", impl=ip2_mxu.conv2d_ip2,
    footprint_fn=ip2_mxu.footprint, uses_mxu=True, max_operand_bits=32,
    outputs_per_pass=1, tags=("paper:Conv2",),
    description="One MXU dot per tap and output row; minimal vector "
                "logic."))
CONV2D.register(KernelIP(
    name="conv2d.ip3_packed", family="conv2d", impl=ip3_packed.conv2d_ip3,
    footprint_fn=ip3_packed.footprint, uses_mxu=False, max_operand_bits=8,
    outputs_per_pass=2, supports_dtypes=("int8",),
    tags=("paper:Conv3", "packed", "dual-stream"),
    description="Operand packing: two 8-bit convolutions per multiplier."))
CONV2D.register(KernelIP(
    name="conv2d.ip4_dual", family="conv2d", impl=ip4_dual.conv2d_ip4,
    footprint_fn=ip4_dual.footprint, uses_mxu=True, max_operand_bits=32,
    outputs_per_pass=2, tags=("paper:Conv4", "dual-stream"),
    # Mosaic: "infer-vector-layout: unsupported shape cast" on int8
    compiled_dtypes=("bfloat16", "float32"),
    description="Two parallel convolutions via dual MXU passes; full precision."))

# --------------------------------------------------------------------------
# pool2d family — the paper's future-work coverage: same resource split as
# Conv1/Conv2 (logic-only windowed reduce vs im2col + one MXU pass).
# Mosaic compiles both for 32-bit operands only: it has no strided load
# of 8- or 16-bit data, no int8 max reduce and no int32 MXU dot, so on
# the TPU a pool site stops at the 16-bit (fake-quant, f32) rung.
# --------------------------------------------------------------------------
POOL2D = IPFamily("pool2d", reference=pool2d_ref)
POOL2D.register(KernelIP(
    name="pool2d.pool_vpu", family="pool2d", impl=pool_vpu_mod.pool2d_window,
    footprint_fn=pool_vpu_mod.footprint, uses_mxu=False,
    compiled_dtypes=("float32",),
    tags=("analogue:Conv1", "windowed-reduce"),
    description="Unrolled strided-slice window reduce; pure VPU, "
                "minimal VMEM."))
POOL2D.register(KernelIP(
    name="pool2d.pool_im2col", family="pool2d",
    impl=pool_im2col_mod.pool2d_im2col,
    footprint_fn=pool_im2col_mod.footprint, uses_mxu=True,
    compiled_dtypes=("float32",), tags=("analogue:Conv2", "im2col"),
    description="Patch tensor in VMEM; avg collapses to one MXU pass, "
                "max to one vectorized reduce."))

# --------------------------------------------------------------------------
# activation family — exact transcendental vs the paper's fixed-point
# spirit (256-entry LUT over the saturation range, 8-bit operand ceiling).
# --------------------------------------------------------------------------
ACTIVATION = IPFamily("activation", reference=activation_ref)
ACTIVATION.register(KernelIP(
    name="activation.act_vpu", family="activation",
    impl=act_exact_mod.activation_exact,
    footprint_fn=act_exact_mod.footprint, uses_mxu=False,
    tags=("exact",),
    description="Exact float32 transcendental on the VPU; full precision, "
                "high op count for tanh/gelu."))
ACTIVATION.register(KernelIP(
    name="activation.act_lut", family="activation",
    impl=act_lut_mod.activation_lut,
    footprint_fn=act_lut_mod.footprint, uses_mxu=False,
    max_operand_bits=8, supports_dtypes=("int8", "bfloat16", "float32"),
    tags=("fixed-point", "lut"),
    description="256-entry LUT over the saturation range; ~4 VPU ops and "
                "1-byte streaming per element; saturating kinds only."))

# --------------------------------------------------------------------------
# cnn_fused family — conv -> pool -> activation as ONE launch (the paper's
# future-work integration of pooling/activation with the conv IPs).  One
# member per conv IP style; the planner substitutes a fused site for a
# fusable conv/pool/act triple when the combined footprint fits and wins
# (core/plan.py, fuse=True).
# --------------------------------------------------------------------------
from repro.kernels.fused import cnn_block as fused_mod  # noqa: E402


def _fused_ref(x, w, *, window=(2, 2), stride=None, mode="max",
               kind="relu"):
    """Composite oracle: the three family references chained."""
    return activation_ref(
        pool2d_ref(conv2d_ref(x, w), window=window, stride=stride,
                   mode=mode), kind=kind)


CNN_FUSED = IPFamily("cnn_fused", reference=_fused_ref,
                     fuse_chains=(("conv2d", "pool2d", "activation"),))
CNN_FUSED.register(KernelIP(
    name="cnn_fused.fused_vpu", family="cnn_fused",
    impl=fused_mod.fused_cnn_vpu, footprint_fn=fused_mod.footprint_vpu,
    uses_mxu=False, tags=("fused", "analogue:Conv1"),
    description="Whole CNN block in one launch: Conv1-style VPU MAC, pool "
                "reduce + activation applied to the VMEM-resident tile; "
                "writes only the pooled, activated tensor."))
CNN_FUSED.register(KernelIP(
    name="cnn_fused.fused_mxu", family="cnn_fused",
    impl=fused_mod.fused_cnn_mxu, footprint_fn=fused_mod.footprint_mxu,
    uses_mxu=True, tags=("fused", "analogue:Conv2"),
    description="Whole CNN block in one launch: one MXU dot per tap and "
                "conv row, pool + activation in register; single HBM "
                "write."))

# --------------------------------------------------------------------------
# res_conv family — conv -> bias -> optional residual add -> optional ReLU
# as ONE launch: the unit a residual network repeats.  The planner
# substitutes it for a (conv, add, act), (conv, act) or lone (conv) run of
# a residual graph's sites (those whose conv carries a bias); the add
# family is the shortcut add such a run keeps when it stays unfused.
# --------------------------------------------------------------------------
from repro.kernels.eltwise import add as add_mod  # noqa: E402
from repro.kernels.fused import res_conv as res_conv_mod  # noqa: E402

ADD = IPFamily("add", reference=add_mod.add_ref, quantizable=False)
ADD.register(KernelIP(
    name="add.add_vpu", family="add", impl=add_mod.add_vpu,
    footprint_fn=add_mod.footprint, uses_mxu=False,
    supports_dtypes=("float32",), compiled_dtypes=("float32",),
    tags=("residual",),
    description="Elementwise residual add on the VPU, one pass over HBM."))


def _res_conv_ref(x, w, b, residual=None, *, stride=1, padding=0,
                  relu=True):
    """Composite oracle: conv, bias, add and ReLU in plain jnp."""
    y = conv2d_ref(x, w, stride=stride, padding=padding) + b
    if residual is not None:
        y = y + residual
    return jnp.maximum(y, 0.0) if relu else y


RES_CONV = IPFamily("res_conv", reference=_res_conv_ref, quantizable=False,
                    fuse_chains=(("conv2d", "add", "activation"),
                                 ("conv2d", "activation"), ("conv2d",)))
RES_CONV.register(KernelIP(
    name="res_conv.res_mxu", family="res_conv", impl=res_conv_mod.res_conv,
    footprint_fn=res_conv_mod.footprint, uses_mxu=True,
    supports_dtypes=("float32",), compiled_dtypes=("float32",),
    tags=("fused", "residual", "analogue:Conv2"),
    description="Residual unit in one launch: one MXU dot per tap and "
                "conv row (strided, zero-padded), then bias, shortcut add "
                "and ReLU on the VMEM-resident row; single HBM write."))

# --------------------------------------------------------------------------
# matmul family — the LM-hot-path generalization.
# --------------------------------------------------------------------------
MATMUL = IPFamily("matmul", reference=matmul_ref)
MATMUL.register(KernelIP(
    name="matmul.mm_vpu", family="matmul", impl=mm_mxu_mod.mm_vpu,
    footprint_fn=mm_mxu_mod.footprint_vpu, uses_mxu=False,
    tags=("analogue:Conv1",),
    description="Dot-free broadcast-multiply matmul; VPU only."))
MATMUL.register(KernelIP(
    name="matmul.mm_mxu", family="matmul", impl=mm_mxu_mod.mm_mxu,
    footprint_fn=mm_mxu_mod.footprint_mxu, uses_mxu=True,
    tags=("analogue:Conv2",),
    description="Tiled MXU matmul, f32/int32 VMEM accumulator."))
MATMUL.register(KernelIP(
    name="matmul.mm_dual_shared", family="matmul", impl=mm_dual.mm_dual_shared,
    footprint_fn=lambda m, k, n, **kw: mm_dual.footprint_dual(
        m, k, n, int8=True, **kw),
    uses_mxu=True, max_operand_bits=8, outputs_per_pass=2,
    supports_dtypes=("int8",), tags=("analogue:Conv3", "dual-stream"),
    description="Two int8 streams, one weight fetch, 2x int8 MXU rate."))
MATMUL.register(KernelIP(
    name="matmul.mm_dual_full", family="matmul", impl=mm_dual.mm_dual_full,
    footprint_fn=lambda m, k, n, itemsize=2, **kw: mm_dual.footprint_dual(
        m, k, n, int8=False, itemsize=itemsize, **kw),
    uses_mxu=True, outputs_per_pass=2, tags=("analogue:Conv4", "dual-stream"),
    description="Two full-precision streams sharing one weight fetch."))

# --------------------------------------------------------------------------
# attention family.
# --------------------------------------------------------------------------
# No integer kernels exist for attention — the precision ladder must
# never lower its sites (quantizable=False; see IPFamily docstring).
ATTENTION = IPFamily("attention", reference=attention_ref, quantizable=False)
ATTENTION.register(KernelIP(
    name="attention.attn_naive", family="attention", impl=attention_ref,
    footprint_fn=lambda b, hq, hkv, sq, skv, d, **kw: attn_flash_mod.footprint(
        b, hq, hkv, sq, skv, d, bq=sq, bk=skv, **kw),
    uses_mxu=True, tags=("reference",),
    description="Materialized-scores attention; VMEM O(S^2) — small S only."))
ATTENTION.register(KernelIP(
    name="attention.attn_flash", family="attention",
    impl=attn_flash_mod.flash_attention,
    footprint_fn=attn_flash_mod.footprint, uses_mxu=True,
    tags=("train", "prefill"),
    description="Tiled online-softmax; VMEM O(block), HBM O(S*D)."))
ATTENTION.register(KernelIP(
    name="attention.attn_decode", family="attention",
    impl=attn_decode_mod.flash_decode,
    footprint_fn=attn_decode_mod.footprint, uses_mxu=True,
    tags=("decode",),
    description="Single-token flash-decode over KV blocks; HBM-bound."))

# --------------------------------------------------------------------------
# ssm_scan family — the attention-free recurrence (jamba/rwkv end of the
# spectrum; Conv1-style logic-only contract: zero MXU passes).
# --------------------------------------------------------------------------
from repro.kernels.mamba_scan import scan as mamba_scan_mod  # noqa: E402
from repro.kernels.mamba_scan.ref import selective_scan_ref  # noqa: E402

SSM_SCAN = IPFamily("ssm_scan", reference=selective_scan_ref,
                    quantizable=False)
SSM_SCAN.register(KernelIP(
    name="ssm_scan.selective_vmem", family="ssm_scan",
    impl=mamba_scan_mod.selective_scan,
    footprint_fn=mamba_scan_mod.footprint, uses_mxu=False,
    tags=("analogue:Conv1", "ssm"),
    description="Selective scan with VMEM-resident state: HBM traffic "
                "O(T·(Di+Ds)) vs the scan twin's O(T·Di·Ds)."))

FAMILIES = {f.name: f for f in (CONV2D, POOL2D, ACTIVATION, CNN_FUSED,
                                ADD, RES_CONV, MATMUL, ATTENTION,
                                SSM_SCAN)}

# --------------------------------------------------------------------------
# Site adapters — what makes each family *plannable*.  An adapter maps a
# declarative SiteSpec (shapes + dtype + knobs) to the candidate members
# and footprint arguments the generic engine (core/plan.py) prices; the
# selection/ranking semantics themselves are family-agnostic.
# --------------------------------------------------------------------------
import math  # noqa: E402

import jax.numpy as jnp  # noqa: E402

from repro.core.ip import SiteRequest, SiteSpec  # noqa: E402


def _bits(dtype) -> int:
    return jnp.dtype(dtype).itemsize * 8


def _conv2d_adapter(spec: SiteSpec) -> SiteRequest:
    x_shape, w_shape = spec.shapes
    n, h, w_, cin = x_shape
    kh, kw, _, cout = w_shape
    want = (("conv2d.ip3_packed", "conv2d.ip4_dual")
            if spec.knob("dual", False)
            else ("conv2d.ip1_vpu", "conv2d.ip2_mxu"))
    if spec.knob("style") == "mxu":
        # a site pinned to the MXU-style member, where the uncalibrated
        # cost model would rank the VPU member first (ROADMAP S5)
        want = ("conv2d.ip2_mxu",)
    # stride, padding and bias reach the single-stream members'
    # footprints only where a site sets them, so a VALID stride-1 site
    # without a bias prices as it always has
    geometry = tuple((k, spec.knob(k)) for k in ("stride", "padding", "bias")
                     if spec.knob(k) is not None)
    return SiteRequest(
        candidates=tuple(CONV2D[name] for name in want),
        fp_args=(n, h, w_, cin, kh, kw, cout),
        fp_kwargs=(("itemsize", jnp.dtype(spec.dtype).itemsize),)
        + geometry,
        op_bits=_bits(spec.dtype))


def _pool2d_adapter(spec: SiteSpec) -> SiteRequest:
    from repro.kernels.pool2d.ref import check_pool_geometry
    (x_shape,) = spec.shapes
    # a padded site (``padding=``: zeros on every side, which the max of
    # a non-negative input ignores) is priced on its padded input
    n, h, w_, c = x_shape
    pad = spec.knob("padding", 0)
    h, w_ = h + 2 * pad, w_ + 2 * pad
    (kh, kw), (sh, sw) = check_pool_geometry(
        (n, h, w_, c), spec.knob("window", (2, 2)), spec.knob("stride"))
    return SiteRequest(
        candidates=(POOL2D["pool2d.pool_vpu"], POOL2D["pool2d.pool_im2col"]),
        fp_args=(n, h, w_, c, kh, kw, sh, sw),
        fp_kwargs=(("itemsize", jnp.dtype(spec.dtype).itemsize),
                   ("mode", spec.knob("mode", "max"))),
        op_bits=_bits(spec.dtype))


def _activation_adapter(spec: SiteSpec) -> SiteRequest:
    kind = spec.knob("kind", "relu")
    cands = [ACTIVATION["activation.act_vpu"]]
    if kind in act_lut_mod.SUPPORTED_KINDS:
        # capability filter: the LUT is constant-off-range, so only
        # saturating kinds may offer it
        cands.append(ACTIVATION["activation.act_lut"])
    n_elems = int(math.prod(int(d) for d in spec.shapes[0]))
    # Activation IPs re-encode their input on ingest (the LUT member
    # quantizes), so the caller's dtype imposes no operand-width floor;
    # precision demands arrive via budget.precision_bits instead.
    return SiteRequest(
        candidates=tuple(cands),
        fp_args=(n_elems,),
        fp_kwargs=(("itemsize", jnp.dtype(spec.dtype).itemsize),
                   ("kind", kind),
                   ("lanes", int(spec.shapes[0][-1]) if spec.shapes[0]
                    else 1)),
        op_bits=0)


def _matmul_adapter(spec: SiteSpec) -> SiteRequest:
    a_shape, b_shape = spec.shapes
    m, k = a_shape[-2], a_shape[-1]
    n = b_shape[-1]
    want = (("matmul.mm_dual_shared", "matmul.mm_dual_full")
            if spec.knob("dual", False)
            else ("matmul.mm_vpu", "matmul.mm_mxu"))
    return SiteRequest(
        candidates=tuple(MATMUL[name] for name in want),
        fp_args=(m, k, n),
        fp_kwargs=(("itemsize", jnp.dtype(spec.dtype).itemsize),),
        op_bits=_bits(spec.dtype))


def _attention_adapter(spec: SiteSpec) -> SiteRequest:
    q_shape, kv_shape = spec.shapes
    b, hq, sq, d = q_shape
    _, hkv, skv, _ = kv_shape
    if sq == 1:
        cands = (ATTENTION["attention.attn_decode"],)
        args = (b, hq, hkv, skv, d)
    else:
        cands = (ATTENTION["attention.attn_naive"],
                 ATTENTION["attention.attn_flash"])
        args = (b, hq, hkv, sq, skv, d)
    return SiteRequest(
        candidates=cands, fp_args=args,
        fp_kwargs=(("itemsize", jnp.dtype(spec.dtype).itemsize),),
        op_bits=_bits(spec.dtype))


def _cnn_fused_adapter(spec: SiteSpec) -> SiteRequest:
    from repro.kernels.pool2d.ref import check_pool_geometry
    x_shape, w_shape = spec.shapes
    n, h, w_, cin = x_shape
    kh, kw, _, cout = w_shape
    conv_out = (n, h - kh + 1, w_ - kw + 1, cout)
    (ph, pw), (sh, sw) = check_pool_geometry(
        conv_out, spec.knob("window", (2, 2)), spec.knob("stride"))
    return SiteRequest(
        candidates=(CNN_FUSED["fused_vpu"], CNN_FUSED["fused_mxu"]),
        fp_args=(n, h, w_, cin, kh, kw, cout, ph, pw, sh, sw),
        fp_kwargs=(("itemsize", jnp.dtype(spec.dtype).itemsize),
                   ("mode", spec.knob("mode", "max")),
                   ("kind", spec.knob("kind", "relu"))),
        op_bits=_bits(spec.dtype))


def _cnn_fuse_sites(run) -> "SiteSpec | None":
    """Map an adjacent (conv, pool, act) SiteSpec triple to the single
    fused-block SiteSpec, or None when the run is not fusable: a
    dual-stream conv, shapes that do not chain conv->pool->act, or a
    pool window the conv output cannot host."""
    conv, pool, act = run
    if (conv.knob("dual", False) or conv.knob("stride", 1) != 1
            or conv.knob("padding", 0)):
        return None
    x_shape, w_shape = conv.shapes
    n, h, w_, cin = x_shape
    kh, kw, _, cout = w_shape
    conv_out = (n, h - kh + 1, w_ - kw + 1, cout)
    if tuple(pool.shapes[0]) != conv_out:
        return None
    try:
        from repro.kernels.pool2d.ref import (check_pool_geometry,
                                              pool2d_out_shape)
        window, stride = check_pool_geometry(
            conv_out, pool.knob("window", (2, 2)), pool.knob("stride"))
        if tuple(act.shapes[0]) != pool2d_out_shape(conv_out, window,
                                                    stride):
            return None
    except ValueError:
        return None
    base = conv.name[:-len(".conv")] if conv.name.endswith(".conv") \
        else conv.name
    ladder = set(conv.ladder) & set(pool.ladder) & set(act.ladder)
    return SiteSpec.make(
        f"{base}.fused", "cnn_fused", (x_shape, w_shape), conv.dtype,
        ladder=tuple(ladder), window=window, stride=stride,
        mode=pool.knob("mode", "max"), kind=act.knob("kind", "relu"))


def _add_adapter(spec: SiteSpec) -> SiteRequest:
    x_shape, _ = spec.shapes
    return SiteRequest(
        candidates=(ADD["add.add_vpu"],), fp_args=tuple(x_shape),
        fp_kwargs=(("itemsize", jnp.dtype(spec.dtype).itemsize),),
        op_bits=_bits(spec.dtype))


def _res_conv_adapter(spec: SiteSpec) -> SiteRequest:
    x_shape, w_shape = spec.shapes
    n, h, w_, cin = x_shape
    kh, kw, _, cout = w_shape
    return SiteRequest(
        candidates=(RES_CONV["res_conv.res_mxu"],),
        fp_args=(n, h, w_, cin, kh, kw, cout),
        fp_kwargs=(("itemsize", jnp.dtype(spec.dtype).itemsize),
                   ("stride", spec.knob("stride", 1)),
                   ("padding", spec.knob("padding", 0)),
                   ("residual", spec.knob("residual", False)),
                   ("relu", spec.knob("relu", True))),
        op_bits=_bits(spec.dtype))


def _conv_out_shape(conv: SiteSpec):
    """(N, Ho, Wo, Cout) a conv2d site writes."""
    from repro.kernels.conv2d.inner import conv_out_hw
    (n, h, w_, _), (kh, kw, _, cout) = conv.shapes
    return (n, *conv_out_hw(h, w_, kh, kw, conv.knob("stride", 1),
                            conv.knob("padding", 0)), cout)


def _res_fuse_sites(run) -> "SiteSpec | None":
    """Map a residual graph's (conv, add, act), (conv, act) or lone
    (conv) run — a projection shortcut — to the single ``res_conv``
    site, or None when it is not fusable: a conv without a bias (not a
    residual graph's), a dual-stream conv, an activation other than
    ReLU, or shapes that do not chain."""
    conv, *rest = run
    if conv.knob("dual", False) or not conv.knob("bias", False):
        return None
    out = _conv_out_shape(conv)
    if rest and (rest[-1].knob("kind", "relu") != "relu"
                 or tuple(rest[-1].shapes[0]) != out):
        return None
    residual = len(rest) == 2
    if residual and any(tuple(s) != out for s in rest[0].shapes):
        return None
    base = conv.name[:-len(".conv")] if conv.name.endswith(".conv") \
        else conv.name
    return SiteSpec.make(
        f"{base}.fused", "res_conv", conv.shapes, conv.dtype,
        stride=conv.knob("stride", 1), padding=conv.knob("padding", 0),
        residual=residual, relu=bool(rest))


CONV2D.site_adapter = _conv2d_adapter
POOL2D.site_adapter = _pool2d_adapter
ACTIVATION.site_adapter = _activation_adapter
CNN_FUSED.site_adapter = _cnn_fused_adapter
CNN_FUSED.fuse_sites = _cnn_fuse_sites
ADD.site_adapter = _add_adapter
RES_CONV.site_adapter = _res_conv_adapter
RES_CONV.fuse_sites = _res_fuse_sites
MATMUL.site_adapter = _matmul_adapter
ATTENTION.site_adapter = _attention_adapter


def get_family(name: str) -> IPFamily:
    return FAMILIES[name]


def get_ip(qualified: str) -> KernelIP:
    family, _, short = qualified.partition(".")
    return FAMILIES[family][short or qualified]
