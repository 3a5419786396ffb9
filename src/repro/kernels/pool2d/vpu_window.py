"""Pool1 — windowed-reduce pooling on the VPU (Conv1-style logic-only IP).

The kernel body issues no dot op: the KHxKW window reduction runs as an
unrolled chain of strided row loads and compares (max) or adds (avg) —
one VPU op per tap per output element, zero MXU passes.  This is the
member the selector picks when the MXU is spoken for, mirroring the
paper's "suitable for FPGAs with limited DSPs".

Tiling: grid over (channel tiles, batch, output-row blocks).  A grid
step holds the input rows its output rows need (element-indexed, so
overlapping windows get their halo) and one output block.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.resources import Footprint, cost_cycles, vpu_op_cycles
from repro.kernels import pallas_call, tile_bytes
from repro.kernels.conv2d.inner import BLOCK_ROWS, for_rows
from repro.kernels.pool2d.ref import norm_window_stride, pool_dtypes


def window_reduce(load, *, kh, kw, mode, acc_dtype):
    """The family's windowed reduce for one output row: ``load(i, j)``
    returns tap ``(i, j)`` — input row ``i`` of the window, columns
    ``j, j + sw, ...`` — as ``(Wo, C)``.  An unrolled chain of compares
    (max) or adds (avg).  Shared verbatim by the standalone kernel below
    and the fused conv->pool->act members (``kernels/fused/cnn_block.py``)
    so the two paths cannot drift."""
    acc = None
    for i in range(kh):
        for j in range(kw):
            win = load(i, j)
            if mode == "avg":
                win = win.astype(acc_dtype)
            if acc is None:
                acc = win
            elif mode == "max":
                acc = jnp.maximum(acc, win)
            else:
                acc = acc + win
    if mode == "avg":
        count = kh * kw
        if jnp.issubdtype(acc_dtype, jnp.integer):
            acc = acc // count
        else:
            acc = acc / count
    return acc


def col_slice(j: int, n: int, stride: int):
    """Columns ``j, j + stride, ...`` (``n`` of them) — a strided load
    on the sublane axis."""
    return pl.ds(j, n, stride=stride) if stride > 1 else pl.ds(j, n)


def pool_geometry(h, w, kh, kw, sh, sw):
    """(Ho, Wo, rows per block, row blocks, input rows per block)."""
    ho, wo = (h - kh) // sh + 1, (w - kw) // sw + 1
    tp = max(1, min(BLOCK_ROWS, ho))
    return ho, wo, tp, -(-ho // tp), (tp - 1) * sh + kh


def pool_call(kernel, x, *, window, stride, block_c, vmem_bytes,
              out_dtype):
    """Launch a row-blocked pooling kernel over (N, H, W, C)."""
    (kh, kw), (sh, sw) = norm_window_stride(window, stride)
    n, h, w, c = x.shape
    ho, wo, tp, n_rb, rows_in = pool_geometry(h, w, kh, kw, sh, sw)
    bc = min(block_c, c)
    cp = -(-c // bc) * bc
    rows = (n_rb - 1) * tp * sh + rows_in
    if rows > h or cp > c:
        x = jnp.pad(x, ((0, 0), (0, max(rows - h, 0)), (0, 0), (0, cp - c)))
    out = pallas_call(
        kernel, grid=(cp // bc, n, n_rb), vmem_bytes=vmem_bytes,
        in_specs=[pl.BlockSpec(
            (pl.Element(1), pl.Element(rows_in), pl.Element(w),
             pl.Element(bc)),
            # a lone channel tile starts at lane 0: say so, so the
            # compiler need not prove a sub-128 tile's offsets aligned
            (lambda ci, b, r: (b, r * tp * sh, 0, 0)) if cp == bc
            else (lambda ci, b, r: (b, r * tp * sh, 0, ci * bc)))],
        out_specs=pl.BlockSpec((1, tp, wo, bc),
                               lambda ci, b, r: (b, r, 0, ci)),
        out_shape=jax.ShapeDtypeStruct((n, n_rb * tp, wo, cp), out_dtype),
    )(x)
    return out[:, :ho, :, :c]


def _kernel(x_ref, o_ref, *, kh, kw, sh, sw, mode, acc_dtype):
    # x_ref: (1, rows_in, W, bc); o_ref: (1, tp, Wo, bc)
    wo = o_ref.shape[2]

    def row(p):
        o_ref[0, p] = window_reduce(
            lambda i, j: x_ref[0, p * sh + i, col_slice(j, wo, sw), :],
            kh=kh, kw=kw, mode=mode, acc_dtype=acc_dtype)

    for_rows(o_ref.shape[1], row)


def window_vmem(h, w, c, kh, kw, sh, sw, *, itemsize, mode, block_c,
                out_item):
    """Double-buffered input rows and output block of one grid step,
    plus the avg accumulator."""
    _, wo, tp, _, rows_in = pool_geometry(h, w, kh, kw, sh, sw)
    bc = min(block_c, c)
    acc = 0 if mode == "max" else 2 * tile_bytes((wo, bc), 4)
    return (2 * tile_bytes((rows_in, w, bc), itemsize)
            + 2 * tile_bytes((tp, wo, bc), out_item) + acc)


@functools.partial(jax.jit,
                   static_argnames=("window", "stride", "mode", "block_c"))
def pool2d_window(x: jnp.ndarray, *, window=(2, 2), stride=None,
                  mode: str = "max", block_c: int = 128) -> jnp.ndarray:
    (kh, kw), (sh, sw) = norm_window_stride(window, stride)
    acc_dtype, out_dtype = pool_dtypes(x.dtype, mode)
    n, h, w, c = x.shape
    vmem = window_vmem(h, w, c, kh, kw, sh, sw, itemsize=x.dtype.itemsize,
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
    out_item = itemsize if mode == "max" else 4
    vmem = window_vmem(h, w, c, kh, kw, sh, sw, itemsize=itemsize,
                       mode=mode, block_c=block_c,
                       out_item=out_item)
    hbm = n * h * w * c * itemsize + n * ho * wo * c * out_item
    # One compare/add per tap, plus the strided gather for each window.
    vpu = 2 * n * ho * wo * c * kh * kw
    return Footprint(vmem_bytes=vmem, hbm_bytes=hbm, mxu_passes=0,
                     vpu_ops=vpu,
                     est_cycles=cost_cycles(vpu_op_cycles(vpu), hbm),
                     outputs_per_pass=1, max_operand_bits=32)
