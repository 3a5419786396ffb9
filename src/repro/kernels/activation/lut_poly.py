"""Act2 — low-precision LUT activation (the paper's fixed-point IP, here).

In the spirit of the paper's 8-bit fixed-point VHDL IPs: the input is
quantized to a 256-level grid over the activation's saturation range and
the nonlinearity becomes a single table lookup — ~4 cheap VPU ops per
element instead of a transcendental, and (in deployment) 1-byte operand
streaming instead of 2-4-byte floats.  Only saturating activations are
supported (relu6/sigmoid/tanh): outside the tabulated range they are
constant, so clipping the index is exact there; unbounded kinds
(relu/gelu) would be wrong beyond the range and are left to the exact
member — capability filtering the selector enforces.

Accuracy: worst-case error is half a quantization step times the
activation's Lipschitz constant plus the saturation tail — ≤ ~0.04 for
the supported kinds (asserted against the oracle in tests).

The table itself is built on the host from the family's ``ref.py``
oracle, so the approximation can never drift from the reference.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.resources import (LANE, Footprint, cost_cycles,
                                  vpu_op_cycles)
from repro.kernels import pallas_call, tile_bytes
from repro.kernels.activation.ref import activation_ref

TABLE_SIZE = 256

# Saturation range per supported kind: |x| > range -> the activation is
# (numerically) constant, so index clipping is exact there.
RANGES = {"relu6": 8.0, "sigmoid": 8.0, "tanh": 4.0}
SUPPORTED_KINDS = tuple(sorted(RANGES))


def build_table(kind: str) -> jnp.ndarray:
    """256-entry float32 table sampled from the ref.py oracle."""
    r = RANGES[kind]
    xs = jnp.linspace(-r, r, TABLE_SIZE, dtype=jnp.float32)
    return activation_ref(xs, kind=kind)


def _kernel(x_ref, t_ref, o_ref, *, r, out_dtype):
    # x_ref, o_ref: (bm, LANE); t_ref: (TABLE_SIZE // LANE, LANE).  The
    # lookup is one lane gather per 128-entry half of the table (the
    # TPU gathers along lanes within a vreg row), then a select.
    x = x_ref[...].astype(jnp.float32)
    scale = (TABLE_SIZE - 1) / (2.0 * r)
    q = jnp.clip(jnp.round((x + r) * scale), 0, TABLE_SIZE - 1)
    q = q.astype(jnp.int32)
    lo = q % LANE
    y = None
    for half in range(TABLE_SIZE // LANE):
        row = jnp.broadcast_to(t_ref[half:half + 1, :], x.shape)
        g = jnp.take_along_axis(row, lo, axis=1, mode="promise_in_bounds")
        y = g if y is None else jnp.where(q >= half * LANE, g, y)
    o_ref[...] = y.astype(out_dtype)


@functools.partial(jax.jit, static_argnames=("kind", "block_rows"))
def activation_lut(x: jnp.ndarray, *, kind: str = "tanh",
                   block_rows: int = 256) -> jnp.ndarray:
    if kind not in RANGES:
        raise ValueError(
            f"LUT activation supports saturating kinds {SUPPORTED_KINDS}; "
            f"{kind!r} is unbounded — use the exact IP")
    out_dtype = (x.dtype if jnp.issubdtype(x.dtype, jnp.floating)
                 else jnp.float32)
    table = build_table(kind).reshape(TABLE_SIZE // LANE, LANE)
    # elementwise: any layout will do, so view x as full 128-lane rows
    n = x.size
    m = -(-n // LANE)
    bm = min(block_rows, m)
    flat = x.reshape(-1)
    if m * LANE != n:
        flat = jnp.pad(flat, (0, m * LANE - n))
    y2 = pallas_call(
        functools.partial(_kernel, r=RANGES[kind], out_dtype=out_dtype),
        grid=(pl.cdiv(m, bm),),
        vmem_bytes=_vmem(bm, x.dtype.itemsize),
        in_specs=[pl.BlockSpec((bm, LANE), lambda i: (i, 0)),
                  pl.BlockSpec(table.shape, lambda i: (0, 0))],
        out_specs=pl.BlockSpec((bm, LANE), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((m, LANE), out_dtype),
    )(flat.reshape(m, LANE), table)
    return y2.reshape(-1)[:n].reshape(x.shape)


def _vmem(bm, itemsize) -> int:
    """Double-buffered (bm, 128) in and f32 out tiles, the table, and
    the body's f32 index, broadcast table row and gathered values."""
    return (2 * tile_bytes((bm, LANE), itemsize)
            + 2 * tile_bytes((bm, LANE), 4)
            + 2 * tile_bytes((TABLE_SIZE // LANE, LANE), 4)
            + 4 * tile_bytes((bm, LANE), 4))


def footprint(n_elems, *, itemsize=4, kind="tanh", block_rows: int = 256,
              lanes: int = LANE) -> Footprint:
    """``lanes`` (the input's trailing dim, which the planner passes to
    every activation member) does not matter here: the kernel views any
    input as full 128-lane rows."""
    del lanes
    bm = max(1, min(block_rows, -(-n_elems // LANE)))
    vmem = _vmem(bm, itemsize)
    # Deployment story: operands stream as 1-byte fixed-point codes
    # (quantize at the producer, dequantize at the consumer) plus the table.
    hbm = n_elems * 2 + TABLE_SIZE * 4
    vpu = n_elems * 4            # scale, clip, round, gather
    return Footprint(vmem_bytes=vmem, hbm_bytes=hbm, mxu_passes=0,
                     vpu_ops=vpu,
                     est_cycles=cost_cycles(vpu_op_cycles(vpu), hbm),
                     outputs_per_pass=1, max_operand_bits=8)
