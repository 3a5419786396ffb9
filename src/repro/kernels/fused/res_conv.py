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

A 1x1 conv with no padding is a plain matmul over the ``N * Ho * Wo``
output pixels, and the row body would make one dot per output row.  So
such a unit takes the flat body instead: each grid step makes ONE
``(TM, Cin) x (Cin, bc)`` dot over the same block of rows, merged into
M in VMEM, and applies the bias, the shortcut and the ReLU to the
``(TM, bc)`` result there.  The block is ``Wo`` rounded up to the 8
sublanes wide, so the merge moves no data; the columns past ``Wo`` are
neither read from HBM nor written back.  A strided 1x1 unit reads every
``stride``-th row and column, taken in XLA with one strided slice.  The
grid walks the row blocks and the Cout tiles in the order that re-reads
the fewer HBM bytes.

The kernel carries the stable name ``res_conv`` (``pallas_call(name=)``
and the jitted wrapper's name), the flat body ``res_conv_1x1``: their
device ops can be told apart by name in a profile.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.resources import (F32_HIGHEST_PASSES, Footprint,
                                  cost_cycles, mxu_pass_cycles,
                                  vpu_op_cycles)
from repro.kernels import pallas_call, round_up, tile_bytes
from repro.kernels.conv2d.inner import (BLOCK_ROWS, column_phases,
                                        conv_body_vmem, conv_mxu_cycles,
                                        conv_out_hw, conv_row, for_rows,
                                        pad_input, phase_block_vmem,
                                        phase_specs, phase_widths)

# The kernel's name in a compiled program and in a device profile, and
# the flat 1x1 body's.
KERNEL_NAME = "res_conv"
FLAT_NAME = "res_conv_1x1"


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


def _flat(kh, kw, padding) -> bool:
    """A unit the flat body runs: a 1x1 conv with no padding."""
    return kh == kw == 1 and padding == 0


def _x_resident(n, ho, wo, cin, cout, n_rb, n_c) -> bool:
    """Whether the flat body keeps its input block resident across the
    Cout tiles (and re-reads the weights per row block): where that
    moves no more bytes than keeping the weights resident and re-reading
    the input per Cout tile."""
    return (n * n_rb - 1) * cin * cout <= (n_c - 1) * n * ho * wo * cin


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


def _flat_kernel(x_ref, w_ref, b_ref, *rest, relu, residual):
    # x_ref (1, tr, Wp, Cin); w_ref (1, 1, Cin, bc); b_ref (1, bc); with
    # a shortcut r_ref (1, tr, Wp, bc); o_ref (1, tr, Wp, bc).  Wp is Wo
    # rounded up to the 8 sublanes, so merging the block's pixels into M
    # moves no data; the columns past Wo hold no input and are not
    # written back.
    r_ref, o_ref = rest if residual else (None, rest[0])
    tm = o_ref.shape[1] * o_ref.shape[2]
    y = jnp.dot(x_ref[...].reshape(tm, x_ref.shape[3]), w_ref[0, 0],
                preferred_element_type=jnp.float32,
                precision=jax.lax.Precision.HIGHEST) + b_ref[...]
    if r_ref is not None:
        y = y + r_ref[...].reshape(tm, o_ref.shape[3])
    if relu:
        y = jnp.maximum(y, 0.0)
    o_ref[...] = y.reshape(o_ref.shape)


def _flat_call(x, w, b, residual, *, stride, relu, block_cout):
    """The flat body of a 1x1 unit: one dot per (row block, Cout tile)
    grid step."""
    if stride > 1:
        x = jax.lax.slice(x, (0, 0, 0, 0), x.shape, (1, stride, stride, 1))
    n, h, w_, cin = x.shape
    cout = w.shape[3]
    _, _, tr, n_rb, _, _ = _rows(h, w_, 1, 1, 1, 0)
    bc = min(block_cout, cout)
    n_c = -(-cout // bc)
    x_resident = _x_resident(n, h, w_, cin, cout, n_rb, n_c)
    wp = round_up(w_, 8)

    def at(f):
        # the grid is (row blocks, Cout tiles) with the input resident,
        # else (Cout tiles, row blocks); ``f`` maps (row block, Cout
        # tile) to a block
        return ((lambda m, c: f(m, c)) if x_resident
                else (lambda c, m: f(m, c)))

    out_spec = pl.BlockSpec((1, tr, wp, bc),
                            at(lambda m, c: (m // n_rb, m % n_rb, 0, c)))
    in_specs = [pl.BlockSpec((1, tr, wp, cin),
                             at(lambda m, c: (m // n_rb, m % n_rb, 0, 0))),
                pl.BlockSpec((1, 1, cin, bc), at(lambda m, c: (0, 0, 0, c))),
                pl.BlockSpec((1, bc), at(lambda m, c: (0, c)))]
    operands = [x, w, b.astype(jnp.float32).reshape(1, cout)]
    if residual is not None:
        in_specs.append(out_spec)
        operands.append(residual)
    return pallas_call(
        functools.partial(_flat_kernel, relu=relu,
                          residual=residual is not None),
        grid=(n * n_rb, n_c) if x_resident else (n_c, n * n_rb),
        vmem_bytes=_flat_vmem(h, w_, cin, cout, 1,
                              itemsize=x.dtype.itemsize,
                              block_cout=block_cout,
                              residual=residual is not None),
        in_specs=in_specs, out_specs=out_spec,
        out_shape=jax.ShapeDtypeStruct((n, h, w_, cout), jnp.float32),
        name=FLAT_NAME,
    )(*operands)


@functools.partial(jax.jit, static_argnames=("stride", "padding", "relu",
                                             "block_cout"))
def res_conv(x: jnp.ndarray, w: jnp.ndarray, b: jnp.ndarray,
             residual=None, *, stride: int = 1, padding: int = 0,
             relu: bool = True, block_cout: int = 128) -> jnp.ndarray:
    """``relu?(conv(x, w) + b [+ residual])`` in one launch, float32.

    x: (N, H, W, Cin); w: (KH, KW, Cin, Cout); b: (Cout,); residual:
    None or (N, Ho, Wo, Cout).  A 1x1 conv with no padding runs the flat
    body (``_flat_call``)."""
    n, h, w_, cin = x.shape
    kh, kw, _, cout = w.shape
    if _flat(kh, kw, padding):
        return _flat_call(x, w, b, residual, stride=stride, relu=relu,
                          block_cout=block_cout)
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


def _flat_vmem(h, w, cin, cout, stride, *, itemsize, block_cout,
               residual):
    """One flat grid step: double-buffered input block, weight tile,
    bias, output block and (with a shortcut) shortcut block, plus the
    dot's ``(TM, bc)`` f32 result.  The operands' bf16 parts that an f32
    dot at ``HIGHEST`` makes are the compiler's temporaries, inside the
    headroom ``kernels.vmem_limit`` adds."""
    _, wo, tr, _, _, _ = _rows(h, w, 1, 1, stride, 0)
    bc = min(block_cout, cout)
    blocks = 2 if residual else 1
    return (2 * (tile_bytes((tr, wo, cin), itemsize)
                 + tile_bytes((cin, bc), itemsize) + tile_bytes((1, bc), 4))
            + 2 * blocks * tile_bytes((tr, wo, bc), 4)
            + tile_bytes((tr * round_up(wo, 8), bc), 4))


def _vmem(h, w, cin, kh, kw, cout, stride, padding, *, itemsize,
          block_cout, residual):
    """One grid step: double-buffered input window, weight tile, bias,
    output block and (with a shortcut) shortcut block, plus the conv row
    body; a flat unit's step as ``_flat_vmem`` counts it."""
    if _flat(kh, kw, padding):
        return _flat_vmem(h, w, cin, cout, stride, itemsize=itemsize,
                          block_cout=block_cout, residual=residual)
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
    shortcut read once, the output written once.  A flat unit reads its
    (strided) input once and its weights per pixel tile where the input
    stays resident, else the input per Cout tile and the weights once."""
    if _flat(kh, kw, padding):
        ho, wo, _, n_rb, _, _ = _rows(h, w, 1, 1, stride, 0)
        n_c = -(-cout // min(block_cout, cout))
        out = n * ho * wo * cout * 4
        x_reads, w_reads = ((1, n * n_rb)
                            if _x_resident(n, ho, wo, cin, cout, n_rb, n_c)
                            else (n_c, 1))
        return ((x_reads * n * ho * wo * cin + w_reads * cin * cout)
                * itemsize + cout * 4 + (out if residual else 0) + out)
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
    """One launch: the conv's MXU passes (a flat unit's: one dot of M =
    TM per grid step), the VPU ops (the row body's shifted slices, and
    the epilogue's bias, add and ReLU, one each per output), and the
    bytes ``_hbm`` counts."""
    ho, wo = conv_out_hw(h, w, kh, kw, stride, padding)
    bc = min(block_cout, cout)
    vmem = _vmem(h, w, cin, kh, kw, cout, stride, padding,
                 itemsize=itemsize, block_cout=block_cout, residual=residual)
    hbm = _hbm(n, h, w, cin, kh, kw, cout, stride, padding,
               itemsize=itemsize, block_cout=block_cout, residual=residual)
    vpu = n * ho * wo * cout * (1 + int(residual) + int(relu))
    if _flat(kh, kw, padding):
        _, _, tr, n_rb, _, _ = _rows(h, w, 1, 1, stride, 0)
        passes = F32_HIGHEST_PASSES if itemsize == 4 else 1
        cyc = n * n_rb * -(-cout // bc) * passes * mxu_pass_cycles(
            tr * round_up(wo, 8), cin, bc)
    else:
        cyc = conv_mxu_cycles(n, ho, wo, cin, kh, kw, cout,
                              itemsize=itemsize, block_cout=block_cout)
        vpu += n * ho * wo * kh * kw * cin  # shifted-slice data movement
    return Footprint(vmem_bytes=vmem, hbm_bytes=hbm,
                     mxu_passes=n * -(-cout // bc), vpu_ops=vpu,
                     est_cycles=cost_cycles(max(cyc, vpu_op_cycles(vpu)),
                                            hbm),
                     outputs_per_pass=1, max_operand_bits=32, launches=1)
