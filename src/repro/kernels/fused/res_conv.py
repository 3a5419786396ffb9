"""The fused residual conv member: conv (KxK, stride, zero padding) ->
per-channel bias -> optional residual add -> optional ReLU, in ONE
``pallas_call`` with no pool — the unit a residual network repeats (a
folded batch norm is the bias; the add is the bottleneck's shortcut).

Unfused, each bias, add and ReLU is a pass of its own over the conv
output, in HBM.  Here each conv output row is computed by the shared
MXU row body (``kernels/conv2d/inner.py``, ``conv_row``), and the bias,
the shortcut row and the ReLU are applied to it while it is still in
VMEM: the member reads its input, weights, bias and shortcut once and
writes its output once.

Tiling: the conv members' grid ``(Cout tiles, batch, row blocks)``.  A
block's rows divide the output height exactly, and each row computes
exactly ``Wo`` columns, so the output and the shortcut need no padding
and the result no slicing.  A strided conv reads the column phases of
its padded input (``kernels/conv2d/inner.py``, ``column_phases``).

The kernel carries the stable name ``res_conv`` (``pallas_call(name=)``
and the jitted wrapper's name): its device ops can be told apart by
name in a profile.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.resources import Footprint, cost_cycles, vpu_op_cycles
from repro.kernels import pallas_call, tile_bytes
from repro.kernels.conv2d.inner import (BLOCK_ROWS, column_phases,
                                        conv_body_vmem, conv_mxu_cycles,
                                        conv_out_hw, conv_row, for_rows,
                                        pad_input, phase_block_vmem,
                                        phase_specs, phase_widths)

# The kernel's name in a compiled program and in a device profile.
KERNEL_NAME = "res_conv"


def _rows(h, w, kh, kw, stride, padding):
    """(Ho, Wo, rows per block, row blocks, column-phase widths, input
    rows per block).  The rows per block are the largest divisor of Ho
    not above ``BLOCK_ROWS``; each phase window spans its phase's whole
    width, as Mosaic asks of a block whose width is not a multiple of
    8."""
    ho, wo = conv_out_hw(h, w, kh, kw, stride, padding)
    tr = max(d for d in range(1, min(BLOCK_ROWS, ho) + 1) if ho % d == 0)
    wp = max((wo - 1) * stride + kw, w + 2 * padding)
    return (ho, wo, tr, ho // tr, phase_widths(wp, stride, kw),
            (tr - 1) * stride + kh)


def _kernel(*refs, kh, kw, stride, relu, residual):
    # refs: the input window (1, rows_in, width, Cin), one per column
    # phase; w_ref (kh, kw, Cin, bc); b_ref (1, bc); with a shortcut
    # r_ref (1, tr, Wo, bc); o_ref (1, tr, Wo, bc)
    n_x = len(refs) - 3 - int(residual)
    x_refs, (w_ref, b_ref, *rest) = refs[:n_x], refs[n_x:]
    x_ref = x_refs[0] if stride == 1 else tuple(x_refs)
    r_ref, o_ref = rest if residual else (None, rest[0])
    wo = o_ref.shape[2]

    def row(r):
        y = conv_row(x_ref, w_ref, r, wpad=wo, kh=kh, kw=kw, style="mxu",
                     acc_dtype=jnp.float32, stride=stride) + b_ref[...]
        if r_ref is not None:
            y = y + r_ref[0, r]
        if relu:
            y = jnp.maximum(y, 0.0)
        o_ref[0, r] = y

    for_rows(o_ref.shape[1], row)


@functools.partial(jax.jit, static_argnames=("stride", "padding", "relu",
                                             "block_cout"))
def res_conv(x: jnp.ndarray, w: jnp.ndarray, b: jnp.ndarray,
             residual=None, *, stride: int = 1, padding: int = 0,
             relu: bool = True, block_cout: int = 128) -> jnp.ndarray:
    """``relu?(conv(x, w) + b [+ residual])`` in one launch, float32.

    x: (N, H, W, Cin); w: (KH, KW, Cin, Cout); b: (Cout,); residual:
    None or (N, Ho, Wo, Cout)."""
    n, h, w_, cin = x.shape
    kh, kw, _, cout = w.shape
    ho, wo, tr, n_rb, widths, rows_in = _rows(h, w_, kh, kw, stride,
                                              padding)
    bc = min(block_cout, cout)
    xp = column_phases(pad_input(x, (ho - 1) * stride + kh,
                                 (wo - 1) * stride + kw, padding),
                       stride, kw)
    out_spec = pl.BlockSpec((1, tr, wo, bc), lambda c, i, r: (i, r, 0, c))
    in_specs = [*phase_specs(rows_in, widths, cin, tr * stride),
                pl.BlockSpec((kh, kw, cin, bc), lambda c, i, r: (0, 0, 0, c)),
                pl.BlockSpec((1, bc), lambda c, i, r: (0, c))]
    operands = [*xp, w, b.astype(jnp.float32).reshape(1, cout)]
    if residual is not None:
        in_specs.append(out_spec)
        operands.append(residual)
    return pallas_call(
        functools.partial(_kernel, kh=kh, kw=kw, stride=stride, relu=relu,
                          residual=residual is not None),
        grid=(pl.cdiv(cout, bc), n, n_rb),
        vmem_bytes=_vmem(h, w_, cin, kh, kw, cout, stride, padding,
                         itemsize=x.dtype.itemsize, block_cout=block_cout,
                         residual=residual is not None),
        in_specs=in_specs, out_specs=out_spec,
        out_shape=jax.ShapeDtypeStruct((n, ho, wo, cout), jnp.float32),
        name=KERNEL_NAME,
    )(*operands)


def _vmem(h, w, cin, kh, kw, cout, stride, padding, *, itemsize,
          block_cout, residual):
    """One grid step: double-buffered input window, weight tile, bias,
    output block and (with a shortcut) shortcut block, plus the conv row
    body."""
    _, wo, tr, _, widths, rows_in = _rows(h, w, kh, kw, stride, padding)
    bc = min(block_cout, cout)
    blocks = 2 if residual else 1
    return (phase_block_vmem(rows_in, widths, cin, kh, kw, bc, itemsize)
            + 2 * tile_bytes((1, bc), 4)
            + 2 * blocks * tile_bytes((tr, wo, bc), 4)
            + conv_body_vmem(wo, cin, bc, itemsize, "mxu", kh * kw))


def _hbm(n, h, w, cin, kh, kw, cout, stride, padding, *, itemsize,
         block_cout, residual):
    """Input windows re-read per Cout tile, weights and bias once, the
    shortcut read once, the output written once."""
    ho, wo, tr, n_rb, widths, rows_in = _rows(h, w, kh, kw, stride,
                                              padding)
    tiles = -(-cout // min(block_cout, cout))
    out = n * ho * wo * cout * 4
    return (tiles * n * n_rb * rows_in * sum(widths) * cin * itemsize
            + kh * kw * cin * cout * itemsize + cout * 4
            + (out if residual else 0) + out)


def footprint(n, h, w, cin, kh, kw, cout, *, stride=1, padding=0,
              residual=False, relu=True, itemsize=4,
              block_cout: int = 128) -> Footprint:
    """One launch: the conv's MXU passes, the epilogue's VPU ops (bias,
    add, ReLU, one each per output), and the bytes ``_hbm`` counts."""
    ho, wo = conv_out_hw(h, w, kh, kw, stride, padding)
    bc = min(block_cout, cout)
    vmem = _vmem(h, w, cin, kh, kw, cout, stride, padding,
                 itemsize=itemsize, block_cout=block_cout, residual=residual)
    hbm = _hbm(n, h, w, cin, kh, kw, cout, stride, padding,
               itemsize=itemsize, block_cout=block_cout, residual=residual)
    cyc = conv_mxu_cycles(n, ho, wo, cin, kh, kw, cout, itemsize=itemsize,
                          block_cout=block_cout)
    outs = n * ho * wo * cout
    vpu = (n * ho * wo * kh * kw * cin      # shifted-slice data movement
           + outs * (1 + int(residual) + int(relu)))
    return Footprint(vmem_bytes=vmem, hbm_bytes=hbm,
                     mxu_passes=n * -(-cout // bc), vpu_ops=vpu,
                     est_cycles=cost_cycles(max(cyc, vpu_op_cycles(vpu)),
                                            hbm),
                     outputs_per_pass=1, max_operand_bits=32, launches=1)
