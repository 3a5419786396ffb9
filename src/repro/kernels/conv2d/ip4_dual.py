"""Conv4 — dual-MXU parallel convolution (paper: 2 DSPs, two convs/pass,
full precision).

Parallelism via resource duplication: the two activation streams are
stacked on a batch axis and one batched `dot_general` issues **two MXU
pass groups** — the TPU reading of "two DSP slices running in
parallel".  Full operand width (int8/int16/bf16/f32), unlike Conv3.
The weight tile is fetched once and shared by both streams (the
paper's serial-coefficient-load economy).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from repro import kernels
from repro.core.resources import (Footprint, cost_cycles, hbm_cycles,
                                  mxu_pass_cycles)


def _kernel(xa_ref, xb_ref, w_ref, oa_ref, ob_ref, *, kh: int, kw: int,
            acc_dtype):
    ho, wo = oa_ref.shape[1], oa_ref.shape[2]
    cin = xa_ref.shape[3]

    def im2col(x):
        cols = []
        for i in range(kh):
            for j in range(kw):
                cols.append(x[i:i + ho, j:j + wo, :])
        return jnp.concatenate(cols, axis=-1).reshape(ho * wo, kh * kw * cin)

    patches = jnp.stack([im2col(xa_ref[0]), im2col(xb_ref[0])])  # (2, M, K)
    wmat = w_ref[...].reshape(kh * kw * cin, -1)                 # (K, bc)
    # Batched dot: two parallel MXU pass groups sharing one weight tile.
    acc = lax.dot_general(
        patches, wmat,
        dimension_numbers=(((2,), (0,)), ((), ())),
        preferred_element_type=acc_dtype)                        # (2, M, bc)
    oa_ref[0] = acc[0].reshape(ho, wo, -1)
    ob_ref[0] = acc[1].reshape(ho, wo, -1)


@functools.partial(jax.jit, static_argnames=("block_cout",))
def conv2d_ip4(xa: jnp.ndarray, xb: jnp.ndarray, w: jnp.ndarray, *,
               block_cout: int = 128):
    n, h, w_, cin = xa.shape
    kh, kw, _, cout = w.shape
    ho, wo = h - kh + 1, w_ - kw + 1
    acc_dtype = (jnp.int32 if jnp.issubdtype(xa.dtype, jnp.integer)
                 else jnp.float32)
    bc = min(block_cout, cout)
    grid = (n, pl.cdiv(cout, bc))
    img = pl.BlockSpec((1, h, w_, cin), lambda b, c: (b, 0, 0, 0))
    out = pl.BlockSpec((1, ho, wo, bc), lambda b, c: (b, 0, 0, c))
    return pl.pallas_call(
        functools.partial(_kernel, kh=kh, kw=kw, acc_dtype=acc_dtype),
        grid=grid,
        in_specs=[img, img,
                  pl.BlockSpec((kh, kw, cin, bc), lambda b, c: (0, 0, 0, c))],
        out_specs=[out, out],
        out_shape=[jax.ShapeDtypeStruct((n, ho, wo, cout), acc_dtype),
                   jax.ShapeDtypeStruct((n, ho, wo, cout), acc_dtype)],
        interpret=kernels.interpret(),
    )(xa, xb, w)


def footprint(n, h, w, cin, kh, kw, cout, *, itemsize=1,
              block_cout: int = 128) -> Footprint:
    ho, wo = h - kh + 1, w - kw + 1
    bc = min(block_cout, cout)
    k = kh * kw * cin
    vmem = (2 * h * w * cin * itemsize
            + 2 * ho * wo * k * itemsize
            + k * bc * itemsize
            + 2 * ho * wo * bc * 4)
    hbm = (2 * n * h * w * cin * itemsize
           + kh * kw * cin * cout * itemsize   # weights fetched ONCE
           + 2 * n * ho * wo * cout * 4)
    passes = 2 * n * ((cout + bc - 1) // bc)
    cyc = 2 * n * mxu_pass_cycles(ho * wo, k, cout)
    vpu = 2 * n * ho * wo * k
    return Footprint(vmem_bytes=vmem, hbm_bytes=hbm, mxu_passes=passes,
                     vpu_ops=vpu,
                     est_cycles=cost_cycles(cyc, hbm),
                     outputs_per_pass=2, max_operand_bits=32)
