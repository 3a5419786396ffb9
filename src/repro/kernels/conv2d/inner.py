"""Shared row-blocked conv machinery — the single source of the per-row
convolution math and of the grid every conv-shaped member walks.

The standalone members (``ip1_vpu``, ``ip2_mxu``) and the fused
conv->pool->act members (``kernels/fused/cnn_block.py``) compute the
same conv rows; keeping the row body here means a fused kernel cannot
drift numerically from the standalone IP it absorbs — the fusion tests
assert bitwise equality in float32, and that only holds because both
paths run literally this code.

Tiling (all conv-shaped members): grid ``(Cout tiles, batch, row
blocks)``.  A grid step holds one window of input rows — its block of
output rows plus the ``kh - 1`` halo rows below it, fetched with
element-indexed (overlapping) blocks — and one weight tile, and loops
over its output rows.  The working set is bounded by the row block, not
by the image, so the same member compiles at 32 px and at 224 px.

Stride and zero padding: a padded conv zero-pads its input in XLA
before the launch.  Output row ``r`` reads input rows ``r * stride ..
r * stride + kh - 1``.  Columns are not loaded with a stride (Mosaic
strides a sublane load only over a 128-lane memref): a strided conv
splits the padded input into ``stride`` column phases in XLA, phase
``b`` holding columns ``b, b + stride, ...``, and tap ``j`` reads phase
``j % stride`` at offset ``j // stride``, contiguously.  ``stride=1,
padding=0`` is the VALID stride-1 conv, with the same loads, the same
grid and the same footprint as before either existed.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.resources import F32_HIGHEST_PASSES, mxu_pass_cycles
from repro.kernels import pallas_call, round_up, tile_bytes

# Output rows one grid step computes (pooled rows for the fused members):
# the bound on a step's working set that the footprints price.
BLOCK_ROWS = 8


def acc_dtype_for(dtype):
    """Integer operands accumulate exactly in int32, floats in f32."""
    return jnp.int32 if jnp.issubdtype(dtype, jnp.integer) else jnp.float32


def _dot_precision(dtype):
    # f32 operands take full-precision MXU passes, not the chip's default
    # single bf16 pass; Mosaic refuses HIGHEST for narrower operands
    return jax.lax.Precision.HIGHEST if dtype == jnp.float32 else None


def conv_out_hw(h: int, w: int, kh: int, kw: int, stride: int = 1,
                padding: int = 0):
    """(Ho, Wo) of a conv with zero ``padding`` on every side."""
    return ((h + 2 * padding - kh) // stride + 1,
            (w + 2 * padding - kw) // stride + 1)


def column_phases(xp, stride: int, kw: int):
    """The column phases of a padded input that a ``stride``-strided
    conv with ``kw``-wide taps reads: phase ``b`` holds columns ``b, b +
    stride, ...``.  One phase, the input itself, at stride 1."""
    if stride == 1:
        return (xp,)
    n, h, w, c = xp.shape
    return tuple(jax.lax.slice(xp, (0, 0, b, 0), (n, h, w, c),
                               (1, 1, stride, 1))
                 for b in range(min(stride, kw)))


def phase_widths(wp: int, stride: int, kw: int):
    """Widths of the column phases of a ``wp``-wide padded input."""
    if stride == 1:
        return (wp,)
    return tuple(-(-(wp - b) // stride) for b in range(min(stride, kw)))


def conv_row(x_ref, w_ref, row, *, wpad: int, kh: int, kw: int, style: str,
             acc_dtype, stride: int = 1):
    """One conv output row ``(wpad, bc)`` from input rows
    ``row * stride .. row * stride + kh - 1`` of the resident window
    ``x_ref`` ``(1, rows, wpad + kw - 1, Cin)`` — at ``stride`` > 1 a
    tuple of its column phases (``column_phases``).

    ``style="mxu"`` (Conv2): each tap is one MXU pass over the row.
    ``style="vpu"`` (Conv1): each tap is a broadcast multiply + reduce
    over Cin — pure VPU work, no dot op.
    """
    phases = x_ref if isinstance(x_ref, tuple) else (x_ref,)
    acc = None
    for i in range(kh):
        for j in range(kw):
            xs = phases[j % stride][0, row * stride + i,
                                    pl.ds(j // stride, wpad), :]
            tap = w_ref[i, j]                                   # (Cin, bc)
            if style == "mxu":
                part = jnp.dot(xs, tap, preferred_element_type=acc_dtype,
                               precision=_dot_precision(xs.dtype))
            else:
                part = jnp.sum(xs.astype(acc_dtype)[:, :, None]
                               * tap.astype(acc_dtype)[None, :, :], axis=1)
            acc = part if acc is None else acc + part
    return acc


def row_window_spec(rows_in: int, win: int, cin: int, rows_per_block: int):
    """Element-indexed block over the padded input: block ``r`` of batch
    ``b`` starts at row ``r * rows_per_block`` and spans ``rows_in``
    rows (the block's rows plus its halo)."""
    return pl.BlockSpec(
        (pl.Element(1), pl.Element(rows_in), pl.Element(win), pl.Element(cin)),
        lambda c, b, r: (b, r * rows_per_block, 0, 0))


def pad_input(x, rows: int, cols: int, padding: int = 0):
    """Zero-pad (N, H, W, C) by ``padding`` on every side, then at the
    bottom/right to at least ``rows`` x ``cols``."""
    _, h, w, _ = x.shape
    h, w = h + 2 * padding, w + 2 * padding
    if rows <= h and cols <= w and not padding:
        return x
    return jnp.pad(x, ((0, 0), (padding, padding + max(rows - h, 0)),
                       (padding, padding + max(cols - w, 0)), (0, 0)))


def row_geometry(ho: int, wo: int, kh: int, kw: int, stride: int = 1):
    """(rows per block, row blocks, computed row width, input window
    width, input rows per block) of a conv walking ``ho`` x ``wo``."""
    tr = max(1, min(BLOCK_ROWS, ho))
    wpad = round_up(wo, 8)
    return (tr, -(-ho // tr), wpad, (wpad - 1) * stride + kw,
            (tr - 1) * stride + kh)


def conv_body_vmem(wpad: int, cin: int, bc: int, itemsize: int,
                   style: str, taps: int) -> int:
    """VMEM the conv row body holds live: the f32/int32 accumulator, the
    loaded row slice and the tap, plus for the VPU style one
    ``(wpad, Cin, bc)`` broadcast product per tap — the compiler
    schedules the unrolled taps' products to be live together (at the
    VGG stages it asks for 7-9 of them)."""
    live = (2 * tile_bytes((wpad, bc), 4) + tile_bytes((wpad, cin), itemsize)
            + tile_bytes((cin, bc), itemsize))
    if style == "vpu":
        live += taps * tile_bytes((wpad, cin, bc), 4)
    return live


def conv_mxu_cycles(n: int, rows: int, wo: int, cin: int, kh: int,
                    kw: int, cout: int, *, itemsize: int,
                    block_cout: int) -> float:
    """MXU cycles of the MXU-style row body over ``rows`` conv output
    rows per image: each row of each Cout tile issues ``kh * kw``
    ``(wpad, Cin) x (Cin, bc)`` dots, and an f32 dot at
    ``Precision.HIGHEST`` is ``F32_HIGHEST_PASSES`` bf16 passes."""
    bc = min(block_cout, cout)
    dots = n * rows * kh * kw * -(-cout // bc)
    passes = F32_HIGHEST_PASSES if itemsize == 4 else 1
    return dots * passes * mxu_pass_cycles(round_up(wo, 8), cin, bc)


def phase_block_vmem(rows_in: int, widths, cin: int, kh: int, kw: int,
                     bc: int, itemsize: int) -> int:
    """Double-buffered input windows (one per column phase) + weight
    tile of one grid step."""
    return 2 * (sum(tile_bytes((rows_in, wp, cin), itemsize)
                    for wp in widths)
                + tile_bytes((kh * kw, cin, bc), itemsize))


def conv_block_vmem(rows_in: int, win: int, cin: int, kh: int, kw: int,
                    bc: int, itemsize: int) -> int:
    """Double-buffered input window + weight tile of one grid step."""
    return phase_block_vmem(rows_in, (win,), cin, kh, kw, bc, itemsize)


def phase_specs(rows_in: int, widths, cin: int, rows_per_block: int):
    """One element-indexed row window per column phase."""
    return [row_window_spec(rows_in, wp, cin, rows_per_block)
            for wp in widths]


def for_rows(n: int, body) -> None:
    """Run ``body(row)`` for ``row`` in ``0 .. n - 1`` as a loop (not
    unrolled: compile time stays flat in the block height)."""
    def step(i, carry):
        body(i)
        return carry
    jax.lax.fori_loop(0, n, step, 0)


def conv_vmem(h: int, w: int, cin: int, kh: int, kw: int, cout: int, *,
              itemsize: int, style: str, block_cout: int, stride: int = 1,
              padding: int = 0) -> int:
    """VMEM of one standalone conv grid step: double-buffered input
    window, weight tile and output block, plus the row body."""
    ho, wo = conv_out_hw(h, w, kh, kw, stride, padding)
    bc = min(block_cout, cout)
    tr, _, wpad, win, rows_in = row_geometry(ho, wo, kh, kw, stride)
    widths = phase_widths(max(win, w + 2 * padding), stride, kw)
    return (phase_block_vmem(rows_in, widths, cin, kh, kw, bc, itemsize)
            + 2 * tile_bytes((tr, wo, bc), 4)
            + conv_body_vmem(wpad, cin, bc, itemsize, style, kh * kw))


def _conv_kernel(*refs, kh, kw, style, acc_dtype, stride):
    # refs: the input window (1, rows_in, win, Cin) — at stride > 1 one
    # per column phase — then w_ref (kh, kw, Cin, bc), o_ref (1, tr, Wo,
    # bc)
    *x_refs, w_ref, o_ref = refs
    x_ref = x_refs[0] if stride == 1 else tuple(x_refs)
    wo = o_ref.shape[2]
    wpad = round_up(wo, 8)

    def row(r):
        acc = conv_row(x_ref, w_ref, r, wpad=wpad, kh=kh, kw=kw,
                       style=style, acc_dtype=acc_dtype, stride=stride)
        o_ref[0, r] = acc[:wo]

    for_rows(o_ref.shape[1], row)


def conv_call(x, w, *, style: str, block_cout: int, stride: int = 1,
              padding: int = 0):
    """Row-blocked conv (zero ``padding``, ``stride``) through one
    Pallas launch."""
    n, h, w_, cin = x.shape
    kh, kw, _, cout = w.shape
    ho, wo = conv_out_hw(h, w_, kh, kw, stride, padding)
    acc_dtype = acc_dtype_for(x.dtype)
    bc = min(block_cout, cout)
    tr, n_rb, _, win, rows_in = row_geometry(ho, wo, kh, kw, stride)
    xp = column_phases(pad_input(x, (n_rb * tr - 1) * stride + kh, win,
                                 padding), stride, kw)
    out = pallas_call(
        functools.partial(_conv_kernel, kh=kh, kw=kw, style=style,
                          acc_dtype=acc_dtype, stride=stride),
        grid=(pl.cdiv(cout, bc), n, n_rb),
        vmem_bytes=conv_vmem(h, w_, cin, kh, kw, cout,
                             itemsize=x.dtype.itemsize, style=style,
                             block_cout=block_cout, stride=stride,
                             padding=padding),
        in_specs=[*phase_specs(rows_in, [p.shape[2] for p in xp], cin,
                               tr * stride),
                  pl.BlockSpec((kh, kw, cin, bc),
                               lambda c, b, r: (0, 0, 0, c))],
        out_specs=pl.BlockSpec((1, tr, wo, bc), lambda c, b, r: (b, r, 0, c)),
        out_shape=jax.ShapeDtypeStruct((n, n_rb * tr, wo, cout), acc_dtype),
    )(*xp, w)
    return out if n_rb * tr == ho else out[:, :ho]


def conv_hbm(n: int, h: int, w: int, cin: int, kh: int, kw: int, cout: int,
             *, itemsize: int, block_cout: int, stride: int = 1,
             padding: int = 0) -> int:
    """HBM bytes one standalone conv moves: every Cout tile re-reads the
    input windows (halo rows included), the weights are read once, the
    int32/f32 output is written once."""
    ho, wo = conv_out_hw(h, w, kh, kw, stride, padding)
    bc = min(block_cout, cout)
    _, n_rb, _, win, rows_in = row_geometry(ho, wo, kh, kw, stride)
    tiles = -(-cout // bc)
    return (tiles * n * n_rb * rows_in * win * cin * itemsize
            + kh * kw * cin * cout * itemsize
            + n * ho * wo * cout * 4)
