"""The residual add ``x + r`` as one Pallas launch on the VPU.

A residual network's shortcut add runs here when the planner keeps a
bottleneck unfused; fused, the add happens inside the ``res_conv``
member (``kernels/fused/res_conv.py``) and this kernel is not launched.

Tiling: grid over (channel tiles, batch, row blocks); a block's rows
divide the height exactly, so nothing is padded or sliced.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.resources import Footprint, cost_cycles, vpu_op_cycles
from repro.kernels import pallas_call, tile_bytes
from repro.kernels.conv2d.inner import BLOCK_ROWS


def _rows(h: int) -> int:
    return max(d for d in range(1, min(BLOCK_ROWS, h) + 1) if h % d == 0)


def add_ref(x, r):
    """The family oracle."""
    return x + r


def _kernel(x_ref, r_ref, o_ref):
    o_ref[...] = x_ref[...] + r_ref[...]


@functools.partial(jax.jit, static_argnames=("block_c",))
def add_vpu(x: jnp.ndarray, r: jnp.ndarray, *,
            block_c: int = 128) -> jnp.ndarray:
    n, h, w, c = x.shape
    tr, bc = _rows(h), min(block_c, c)
    spec = pl.BlockSpec((1, tr, w, bc), lambda ci, b, i: (b, i, 0, ci))
    return pallas_call(
        _kernel, grid=(pl.cdiv(c, bc), n, h // tr),
        vmem_bytes=_vmem(h, w, c, x.dtype.itemsize, block_c),
        in_specs=[spec, spec], out_specs=spec,
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype))(x, r)


def _vmem(h, w, c, itemsize, block_c):
    """Three double-buffered blocks."""
    return 6 * tile_bytes((_rows(h), w, min(block_c, c)), itemsize)


def footprint(n, h, w, c, *, itemsize=4, block_c: int = 128) -> Footprint:
    elems = n * h * w * c
    hbm = 3 * elems * itemsize
    return Footprint(vmem_bytes=_vmem(h, w, c, itemsize, block_c),
                     hbm_bytes=hbm, mxu_passes=0, vpu_ops=elems,
                     est_cycles=cost_cycles(vpu_op_cycles(elems), hbm),
                     outputs_per_pass=1, max_operand_bits=32)
