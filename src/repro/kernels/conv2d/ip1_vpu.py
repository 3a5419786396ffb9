"""Conv1 — logic-only convolution (paper: 0 DSP, high LUT/CLB usage).

TPU-native reading: the kernel body issues **no dot op** — every
multiply-accumulate runs on the VPU as a broadcast multiply + reduce
over the input channels of each output row.  High vector-op count, zero
MXU passes.  This is the variant the selector picks when the MXU is
unavailable / saturated (budget.mxu_available=False), exactly the
paper's "suitable for FPGAs with limited DSPs".

Tiling: the shared row-blocked grid (``kernels/conv2d/inner.py``).  The
row body holds a ``(W, Cin, bc)`` broadcast product, which the
footprint counts.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.resources import Footprint, cost_cycles, vpu_op_cycles
from repro.kernels.conv2d.inner import (conv_call, conv_hbm, conv_out_hw,
                                        conv_vmem)


@functools.partial(jax.jit, static_argnames=("block_cout", "stride",
                                             "padding"))
def conv2d_ip1(x: jnp.ndarray, w: jnp.ndarray, *,
               block_cout: int = 128, stride: int = 1,
               padding: int = 0) -> jnp.ndarray:
    return conv_call(x, w, style="vpu", block_cout=block_cout,
                     stride=stride, padding=padding)


def footprint(n, h, w, cin, kh, kw, cout, *, itemsize=1,
              block_cout: int = 128, stride: int = 1, padding: int = 0,
              bias: bool = False) -> Footprint:
    """``bias=True`` prices a site whose per-channel bias the caller
    adds after the launch: one more pass over the output, in HBM."""
    ho, wo = conv_out_hw(h, w, kh, kw, stride, padding)
    vmem = conv_vmem(h, w, cin, kh, kw, cout, itemsize=itemsize,
                     style="vpu", block_cout=block_cout, stride=stride,
                     padding=padding)
    hbm = conv_hbm(n, h, w, cin, kh, kw, cout, itemsize=itemsize,
                   block_cout=block_cout, stride=stride, padding=padding)
    if bias:
        hbm += 2 * n * ho * wo * cout * 4
    vpu = n * ho * wo * cout * kh * kw * cin * 2   # mul+add per tap
    vpu += n * ho * wo * cout if bias else 0
    return Footprint(vmem_bytes=vmem, hbm_bytes=hbm, mxu_passes=0,
                     vpu_ops=vpu,
                     est_cycles=cost_cycles(vpu_op_cycles(vpu), hbm),
                     outputs_per_pass=1, max_operand_bits=32)
