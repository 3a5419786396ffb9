"""Act1 — exact elementwise activation on the VPU (full-precision IP).

Every transcendental is evaluated exactly (to float32 ULP) by the
vector unit: zero MXU passes, but a per-element op count that grows
with the activation's complexity (tanh/gelu cost an order of magnitude
more VPU ops than relu).  This is the member the selector picks when
the deployment demands full precision (budget.precision_bits > 8).

Tiling: the input is viewed as (rows, lanes) and the grid walks row
blocks; each grid step holds one (block_rows, K) tile in VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.resources import Footprint, cost_cycles, vpu_op_cycles
from repro.kernels import pallas_call, tile_bytes
from repro.kernels.activation.ref import _FNS, KINDS

# Approximate VPU scalar-op cost per element (mul/add/cmp units).
OP_COST = {"relu": 1, "relu6": 2, "sigmoid": 10, "tanh": 12, "gelu": 15}


def _kernel(x_ref, o_ref, *, kind, out_dtype):
    y = _FNS[kind](x_ref[...].astype(jnp.float32))
    o_ref[...] = y.astype(out_dtype)


@functools.partial(jax.jit, static_argnames=("kind", "block_rows"))
def activation_exact(x: jnp.ndarray, *, kind: str = "relu",
                     block_rows: int = 256) -> jnp.ndarray:
    if kind not in KINDS:
        raise ValueError(f"unknown activation {kind!r}; have {KINDS}")
    out_dtype = (x.dtype if jnp.issubdtype(x.dtype, jnp.floating)
                 else jnp.float32)
    shape = x.shape
    k = shape[-1] if x.ndim >= 1 and shape else 1
    x2 = x.reshape(-1, k) if x.ndim != 2 else x
    m = x2.shape[0]
    bm = min(block_rows, m)
    y2 = pallas_call(
        functools.partial(_kernel, kind=kind, out_dtype=out_dtype),
        grid=(pl.cdiv(m, bm),),
        vmem_bytes=_vmem(bm, k, x.dtype.itemsize,
                         jnp.dtype(out_dtype).itemsize),
        in_specs=[pl.BlockSpec((bm, k), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((bm, k), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((m, k), out_dtype),
    )(x2)
    return y2.reshape(shape)


def _vmem(bm, k, itemsize, out_item) -> int:
    """Double-buffered (bm, K) in and out tiles plus the f32 body value."""
    return (2 * tile_bytes((bm, k), itemsize)
            + 2 * tile_bytes((bm, k), out_item)
            + tile_bytes((bm, k), 4))


def footprint(n_elems, *, itemsize=4, kind="relu", block_rows: int = 256,
              lanes: int = 128) -> Footprint:
    """``lanes`` is the trailing (channel) dim the kernel tiles over."""
    bm = max(1, min(block_rows, n_elems // max(lanes, 1)))
    vmem = _vmem(bm, min(lanes, n_elems), itemsize, max(itemsize, 4))
    hbm = n_elems * (itemsize + itemsize)          # stream in + out
    vpu = n_elems * OP_COST.get(kind, 8)
    return Footprint(vmem_bytes=vmem, hbm_bytes=hbm, mxu_passes=0,
                     vpu_ops=vpu,
                     est_cycles=cost_cycles(vpu_op_cycles(vpu), hbm),
                     outputs_per_pass=1, max_operand_bits=32)
