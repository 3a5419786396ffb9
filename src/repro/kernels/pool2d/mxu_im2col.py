"""Pool2 — im2col pooling (Conv2-style IP: taps gathered in VMEM).

For ``max`` the KHxKW taps of an output row are stacked into a patch
tensor inside VMEM and reduced with one vectorized max over the tap
axis.  For ``avg`` the window sum collapses into MXU passes: each
window row is multiplied by a 0/1 column-selection matrix ``(Wo, W)``
that sums the ``kw`` columns of every window (int32/f32 accumulation,
matching the oracle's fixed-point floor division).  Minimal per-tap
vector logic at the cost of a larger VMEM working set — the paper's
"ideal for FPGAs with DSP availability and limited logic resources",
pooling edition.

Tiling: the row-blocked grid of ``pool2d/vpu_window.py``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.resources import (Footprint, cost_cycles, mxu_pass_cycles,
                                  vpu_op_cycles)
from repro.kernels import tile_bytes
from repro.kernels.conv2d.inner import for_rows
from repro.kernels.pool2d.ref import norm_window_stride, pool_dtypes
from repro.kernels.pool2d.vpu_window import (col_slice, pool_call,
                                             pool_geometry)


def _kernel(x_ref, o_ref, *, kh, kw, sh, sw, mode, acc_dtype):
    # x_ref: (1, rows_in, W, bc); o_ref: (1, tp, Wo, bc)
    w, wo = x_ref.shape[2], o_ref.shape[2]
    if mode == "avg":
        # sel[q, c] = 1 where column c lies in output column q's window
        q = jax.lax.broadcasted_iota(jnp.int32, (wo, w), 0)
        c = jax.lax.broadcasted_iota(jnp.int32, (wo, w), 1)
        sel = ((c >= q * sw) & (c < q * sw + kw)).astype(acc_dtype)

    def row(p):
        if mode == "max":
            taps = [x_ref[0, p * sh + i, col_slice(j, wo, sw), :]
                    for i in range(kh) for j in range(kw)]
            o_ref[0, p] = jnp.max(jnp.stack(taps), axis=0)
            return
        acc = None
        for i in range(kh):
            part = jnp.dot(sel, x_ref[0, p * sh + i].astype(acc_dtype),
                           preferred_element_type=acc_dtype,
                           precision=jax.lax.Precision.HIGHEST)
            acc = part if acc is None else acc + part
        count = kh * kw
        o_ref[0, p] = (acc // count if jnp.issubdtype(acc_dtype, jnp.integer)
                       else acc / count)

    for_rows(o_ref.shape[1], row)


def im2col_vmem(h, w, c, kh, kw, sh, sw, *, itemsize, mode, block_c,
                out_item):
    """Double-buffered input rows and output block, plus the stacked
    taps (max) or the selection matrix, cast row and accumulator (avg)."""
    _, wo, tp, _, rows_in = pool_geometry(h, w, kh, kw, sh, sw)
    bc = min(block_c, c)
    if mode == "max":
        body = tile_bytes((kh * kw, wo, bc), itemsize)
    else:
        body = (tile_bytes((wo, w), 4) + tile_bytes((w, bc), 4)
                + 2 * tile_bytes((wo, bc), 4))
    return (2 * tile_bytes((rows_in, w, bc), itemsize)
            + 2 * tile_bytes((tp, wo, bc), out_item) + body)


@functools.partial(jax.jit,
                   static_argnames=("window", "stride", "mode", "block_c"))
def pool2d_im2col(x: jnp.ndarray, *, window=(2, 2), stride=None,
                  mode: str = "max", block_c: int = 128) -> jnp.ndarray:
    (kh, kw), (sh, sw) = norm_window_stride(window, stride)
    acc_dtype, out_dtype = pool_dtypes(x.dtype, mode)
    n, h, w, c = x.shape
    vmem = im2col_vmem(h, w, c, kh, kw, sh, sw, itemsize=x.dtype.itemsize,
                       mode=mode, block_c=block_c,
                       out_item=jnp.dtype(out_dtype).itemsize)
    return pool_call(
        functools.partial(_kernel, kh=kh, kw=kw, sh=sh, sw=sw, mode=mode,
                          acc_dtype=acc_dtype),
        x, window=window, stride=stride, block_c=block_c, vmem_bytes=vmem,
        out_dtype=out_dtype)


def footprint(n, h, w, c, kh, kw, sh, sw, *, itemsize=1, mode="max",
              block_c: int = 128) -> Footprint:
    ho, wo = (h - kh) // sh + 1, (w - kw) // sw + 1
    bc = min(block_c, c)
    out_item = itemsize if mode == "max" else 4
    taps = kh * kw
    vmem = im2col_vmem(h, w, c, kh, kw, sh, sw, itemsize=itemsize,
                       mode=mode, block_c=block_c,
                       out_item=out_item)
    hbm = n * h * w * c * itemsize + n * ho * wo * c * out_item
    grid_steps = n * ((c + bc - 1) // bc)
    # Patch construction is pure data movement: one op per tap element.
    move = n * ho * wo * c * taps
    if mode == "avg":
        passes = grid_steps
        cyc = n * ho * kh * mxu_pass_cycles(wo, w, c)
        vpu = move
    else:
        passes = 0
        cyc = 0.0
        vpu = 2 * move          # movement + the vectorized max reduce
    return Footprint(vmem_bytes=vmem, hbm_bytes=hbm, mxu_passes=passes,
                     vpu_ops=vpu,
                     est_cycles=cost_cycles(max(cyc, vpu_op_cycles(vpu)), hbm),
                     outputs_per_pass=1, max_operand_bits=32)
