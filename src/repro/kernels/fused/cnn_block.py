"""Fused conv->pool->activation CNN-block kernels — one resource-shaped
unit per block, the paper's stated future work ("integrate pooling and
activation with the convolution IPs").

The unfused chain launches three ``pallas_call``s and round-trips the
conv output (the largest tensor of the block) and the pool output
through HBM between them.  Each fused member computes the conv
accumulator tile, applies the pooling reduce and the activation to the
still-resident VMEM tile, and writes ONLY the final (pooled, activated)
tensor back — the intermediate reads+writes disappear from the DMA
column, which the additive cost model (``core.resources.cost_cycles``)
turns into a counted est-cycles drop.

Two members, one per conv IP style, sharing the standalone kernels'
inner-loop bodies verbatim (``kernels/conv2d/inner.py``,
``kernels/pool2d/vpu_window.py::window_reduce``) so fused and unfused
numerics cannot drift:

* ``fused_vpu`` — Conv1-style logic-only accumulation; zero MXU passes.
* ``fused_mxu`` — Conv2-style: one MXU dot per tap and conv row.

**int8 rung** (the PR 3 mixed-precision path): ``scale=`` feeds the
combined (activation x per-channel weight) dequantization scale into
the kernel; the int32 conv accumulator is rescaled to float *in
register* and pooling/activation run on the rescaled tile — no
intermediate fixed-point codes are materialized, and the block's single
dequantize happens before its single write.

Tiling: the row-blocked grid of the standalone conv IPs
(``kernels/conv2d/inner.py``), in blocks of pooled output rows.  For each
pooled row the kernel computes the ``ph`` conv rows under its window into
a ``(ph, W, bc)`` VMEM scratch and pools them with the standalone pool
member's reduce — the fused VMEM need is the price the planner weighs
against the saved traffic (docs/adaptive_ips.md, "Fusion contract").
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.resources import Footprint, cost_cycles, vpu_op_cycles
from repro.kernels import pallas_call, round_up, tile_bytes
from repro.kernels.activation.ref import KINDS, _FNS
from repro.kernels.activation.vpu_exact import OP_COST
from repro.kernels.conv2d.inner import (BLOCK_ROWS, acc_dtype_for,
                                        conv_block_vmem, conv_body_vmem,
                                        conv_mxu_cycles, conv_row, for_rows,
                                        pad_input, row_window_spec)
from repro.kernels.pool2d.ref import check_pool_geometry
from repro.kernels.pool2d.vpu_window import col_slice, window_reduce


def _geometry(h, w, kh, kw, ph, pw, sh, sw):
    """(conv Ho, conv Wo, pooled Ho, pooled Wo) of one fused block."""
    co_h, co_w = h - kh + 1, w - kw + 1
    return co_h, co_w, (co_h - ph) // sh + 1, (co_w - pw) // sw + 1


def _rows(h, w, kh, kw, ph, pw, sh, sw):
    """(pooled rows per block, row blocks, computed conv row width,
    input window width, input rows per block)."""
    co_h, co_w, po, _ = _geometry(h, w, kh, kw, ph, pw, sh, sw)
    tp = max(1, min(BLOCK_ROWS, po))
    wpad = round_up(co_w, 8)
    return tp, -(-po // tp), wpad, wpad + kw - 1, (tp - 1) * sh + ph + kh - 1


def _pool_dtype(acc_dtype, scaled: bool):
    # Native-integer blocks keep the family oracle's fixed-point avg
    # (int32 accumulate, floor division); everything else pools in f32.
    return (jnp.int32 if jnp.issubdtype(acc_dtype, jnp.integer)
            and not scaled else jnp.float32)


def _kernel(x_ref, w_ref, *rest, style, kh, kw, ph, pw, sh, sw, mode,
            kind, acc_dtype):
    # rest is (scale_ref, o_ref, s_ref) on the int8 rung, (o_ref, s_ref)
    # otherwise.  x_ref: (1, rows_in, win, Cin); o_ref: (1, tp, Qo, bc);
    # s_ref: (ph, wpad, bc) — the conv rows under one pooled row.
    scale_ref, o_ref, s_ref = rest if len(rest) == 3 else (None, *rest)
    qo, wpad = o_ref.shape[2], s_ref.shape[1]

    def pooled_row(p):
        for a in range(ph):
            acc = conv_row(x_ref, w_ref, p * sh + a, wpad=wpad, kh=kh,
                           kw=kw, style=style, acc_dtype=acc_dtype)
            if scale_ref is not None:
                # The int8 rung's in-register dequantize: int32
                # accumulator -> float via the combined (act x
                # per-channel weight) scale, while the row is still
                # VMEM-resident — no intermediate codes.
                acc = acc.astype(jnp.float32) * scale_ref[...]
            s_ref[a] = acc
        pooled = window_reduce(
            lambda i, j: s_ref[i, col_slice(j, qo, sw), :],
            kh=ph, kw=pw, mode=mode, acc_dtype=s_ref.dtype)
        o_ref[0, p] = _FNS[kind](pooled.astype(jnp.float32))

    for_rows(o_ref.shape[1], pooled_row)


def _fused_call(style, x, w, scale, pool_window, pool_stride, pool_mode,
                act_kind, block_cout):
    if act_kind not in KINDS:
        raise ValueError(f"unknown activation {act_kind!r}; have {KINDS}")
    n, h, w_, cin = x.shape
    kh, kw, _, cout = w.shape
    (ph, pw), (sh, sw) = check_pool_geometry(
        (n, h - kh + 1, w_ - kw + 1, cout), pool_window, pool_stride)
    _, _, po, qo = _geometry(h, w_, kh, kw, ph, pw, sh, sw)
    tp, n_rb, wpad, win, rows_in = _rows(h, w_, kh, kw, ph, pw, sh, sw)
    acc_dtype = acc_dtype_for(x.dtype)
    bc = min(block_cout, cout)
    in_specs = [row_window_spec(rows_in, win, cin, tp * sh),
                pl.BlockSpec((kh, kw, cin, bc), lambda c, b, r: (0, 0, 0, c))]
    operands = [pad_input(x, (n_rb - 1) * tp * sh + rows_in, win), w]
    if scale is not None:
        in_specs.append(pl.BlockSpec((1, bc), lambda c, b, r: (0, c)))
        operands.append(jnp.asarray(scale, jnp.float32).reshape(1, cout))
    vmem = _vmem(style, h, w_, cin, kh, kw, cout, ph, pw, sh, sw,
                 itemsize=x.dtype.itemsize, block_cout=block_cout)
    out = pallas_call(
        functools.partial(_kernel, style=style, kh=kh, kw=kw, ph=ph, pw=pw,
                          sh=sh, sw=sw, mode=pool_mode, kind=act_kind,
                          acc_dtype=acc_dtype),
        grid=(pl.cdiv(cout, bc), n, n_rb), vmem_bytes=vmem,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, tp, qo, bc), lambda c, b, r: (b, r, 0, c)),
        out_shape=jax.ShapeDtypeStruct((n, n_rb * tp, qo, cout), jnp.float32),
        scratch_shapes=[pltpu.VMEM(
            (ph, wpad, bc), _pool_dtype(acc_dtype, scale is not None))],
    )(*operands)
    return out if n_rb * tp == po else out[:, :po]


@functools.partial(jax.jit, static_argnames=(
    "pool_window", "pool_stride", "pool_mode", "act_kind", "block_cout"))
def fused_cnn_vpu(x: jnp.ndarray, w: jnp.ndarray, scale=None, *,
                  pool_window=(2, 2), pool_stride=None,
                  pool_mode: str = "max", act_kind: str = "relu",
                  block_cout: int = 128) -> jnp.ndarray:
    """Logic-only fused block: Conv1-style MAC, pool + act in register.

    ``scale`` (f32, broadcastable to (1, 1, 1, Cout)) switches on the
    int8 rung: integer operands, int32 accumulate, in-register rescale.
    """
    return _fused_call("vpu", x, w, scale, pool_window, pool_stride,
                       pool_mode, act_kind, block_cout)


@functools.partial(jax.jit, static_argnames=(
    "pool_window", "pool_stride", "pool_mode", "act_kind", "block_cout"))
def fused_cnn_mxu(x: jnp.ndarray, w: jnp.ndarray, scale=None, *,
                  pool_window=(2, 2), pool_stride=None,
                  pool_mode: str = "max", act_kind: str = "relu",
                  block_cout: int = 128) -> jnp.ndarray:
    """MXU fused block: one MXU pass per tap, pool + act in register."""
    return _fused_call("mxu", x, w, scale, pool_window, pool_stride,
                       pool_mode, act_kind, block_cout)


def _vmem(style, h, w, cin, kh, kw, cout, ph, pw, sh, sw, *, itemsize,
          block_cout):
    """One grid step: double-buffered input window, weight tile and
    pooled output block, the conv-row scratch, the pooled row, and the
    conv row body."""
    _, _, _, qo = _geometry(h, w, kh, kw, ph, pw, sh, sw)
    tp, _, wpad, win, rows_in = _rows(h, w, kh, kw, ph, pw, sh, sw)
    bc = min(block_cout, cout)
    return (conv_block_vmem(rows_in, win, cin, kh, kw, bc, itemsize)
            + 2 * tile_bytes((tp, qo, bc), 4)
            + tile_bytes((ph, wpad, bc), 4)
            + 2 * tile_bytes((qo, bc), 4)
            + conv_body_vmem(wpad, cin, bc, itemsize, style,
                             ph * kh * kw))


def _hbm(n, h, w, cin, kh, kw, cout, ph, pw, sh, sw, *, itemsize,
         block_cout):
    """Input windows re-read per Cout tile, weights once, ONLY the final
    pooled tensor written."""
    _, _, po, qo = _geometry(h, w, kh, kw, ph, pw, sh, sw)
    _, n_rb, _, win, rows_in = _rows(h, w, kh, kw, ph, pw, sh, sw)
    tiles = -(-cout // min(block_cout, cout))
    return (tiles * n * n_rb * rows_in * win * cin * itemsize
            + kh * kw * cin * cout * itemsize
            + n * po * qo * cout * 4)


# ---------------------------------------------------------------------------
# Footprints — the combined block priced as ONE launch: the conv working
# set plus the pooled tile in VMEM, but ONLY input + weights + final
# output in the DMA column.
# ---------------------------------------------------------------------------
def _pool_act_vpu_ops(n, cout, po, qo, ph, pw, kind):
    pool = 2 * n * po * qo * cout * ph * pw     # gather + compare/add per tap
    act = n * po * qo * cout * OP_COST.get(kind, 8)
    return pool + act


def footprint_vpu(n, h, w, cin, kh, kw, cout, ph, pw, sh, sw, *,
                  itemsize=1, mode="max", kind="relu",
                  block_cout: int = 128) -> Footprint:
    co_h, co_w, po, qo = _geometry(h, w, kh, kw, ph, pw, sh, sw)
    vmem = _vmem("vpu", h, w, cin, kh, kw, cout, ph, pw, sh, sw,
                 itemsize=itemsize, block_cout=block_cout)
    hbm = _hbm(n, h, w, cin, kh, kw, cout, ph, pw, sh, sw,
               itemsize=itemsize, block_cout=block_cout)
    vpu = (n * co_h * co_w * cout * kh * kw * cin * 2
           + _pool_act_vpu_ops(n, cout, po, qo, ph, pw, kind))
    if itemsize == 1:
        vpu += n * co_h * co_w * cout         # in-register rescale
    return Footprint(vmem_bytes=vmem, hbm_bytes=hbm, mxu_passes=0,
                     vpu_ops=vpu,
                     est_cycles=cost_cycles(vpu_op_cycles(vpu), hbm),
                     outputs_per_pass=1, max_operand_bits=32, launches=1)


def footprint_mxu(n, h, w, cin, kh, kw, cout, ph, pw, sh, sw, *,
                  itemsize=1, mode="max", kind="relu",
                  block_cout: int = 128) -> Footprint:
    co_h, co_w, po, qo = _geometry(h, w, kh, kw, ph, pw, sh, sw)
    bc = min(block_cout, cout)
    k = kh * kw * cin
    vmem = _vmem("mxu", h, w, cin, kh, kw, cout, ph, pw, sh, sw,
                 itemsize=itemsize, block_cout=block_cout)
    hbm = _hbm(n, h, w, cin, kh, kw, cout, ph, pw, sh, sw,
               itemsize=itemsize, block_cout=block_cout)
    passes = n * ((cout + bc - 1) // bc)
    # every pooled row computes the ph conv rows under its window
    cyc = conv_mxu_cycles(n, po * ph, co_w, cin, kh, kw, cout,
                          itemsize=itemsize, block_cout=block_cout)
    vpu = (n * co_h * co_w * k                # shifted-slice data movement
           + _pool_act_vpu_ops(n, cout, po, qo, ph, pw, kind))
    if itemsize == 1:
        vpu += n * co_h * co_w * cout
    return Footprint(vmem_bytes=vmem, hbm_bytes=hbm, mxu_passes=passes,
                     vpu_ops=vpu,
                     est_cycles=cost_cycles(max(cyc, vpu_op_cycles(vpu)), hbm),
                     outputs_per_pass=1, max_operand_bits=32, launches=1)
