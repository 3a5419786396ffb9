"""Conv2 — MXU convolution (paper: 1 DSP, low logic).

TPU-native reading: each output row is ``kh * kw`` MXU passes, one per
tap — a shifted row slice of the resident input window times the tap's
``(Cin, bc)`` weight matrix, accumulated in int32/f32.  Minimal vector
logic — the paper's "reduces the use of logic; ideal for FPGAs with DSP
availability and limited logic resources".

Tiling: the shared row-blocked grid (``kernels/conv2d/inner.py``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.resources import Footprint, cost_cycles
from repro.kernels.conv2d.inner import (conv_call, conv_hbm,
                                        conv_mxu_cycles, conv_out_hw,
                                        conv_vmem)


@functools.partial(jax.jit, static_argnames=("block_cout", "stride",
                                             "padding"))
def conv2d_ip2(x: jnp.ndarray, w: jnp.ndarray, *,
               block_cout: int = 128, stride: int = 1,
               padding: int = 0) -> jnp.ndarray:
    return conv_call(x, w, style="mxu", block_cout=block_cout,
                     stride=stride, padding=padding)


def footprint(n, h, w, cin, kh, kw, cout, *, itemsize=1,
              block_cout: int = 128, stride: int = 1, padding: int = 0,
              bias: bool = False) -> Footprint:
    """``bias=True`` prices a site whose per-channel bias the caller
    adds after the launch: one more pass over the output, in HBM."""
    ho, wo = conv_out_hw(h, w, kh, kw, stride, padding)
    bc = min(block_cout, cout)
    k = kh * kw * cin
    vmem = conv_vmem(h, w, cin, kh, kw, cout, itemsize=itemsize,
                     style="mxu", block_cout=block_cout, stride=stride,
                     padding=padding)
    hbm = conv_hbm(n, h, w, cin, kh, kw, cout, itemsize=itemsize,
                   block_cout=block_cout, stride=stride, padding=padding)
    if bias:
        hbm += 2 * n * ho * wo * cout * 4
    passes = n * ((cout + bc - 1) // bc)
    cyc = conv_mxu_cycles(n, ho, wo, cin, kh, kw, cout, itemsize=itemsize,
                          block_cout=block_cout)
    vpu = n * ho * wo * k                     # shifted-slice data movement
    vpu += n * ho * wo * cout if bias else 0
    return Footprint(vmem_bytes=vmem, hbm_bytes=hbm, mxu_passes=passes,
                     vpu_ops=vpu,
                     est_cycles=cost_cycles(cyc, hbm),
                     outputs_per_pass=1, max_operand_bits=32)
