"""Conv3 — operand-packed dual convolution (paper: 1 DSP, two convs/pass,
operands limited to 8 bits).

The paper's signature trick: two 8-bit products share one wide
multiplier.  On the FPGA that is the 27x18 DSP slice; on TPU the VPU's
int32 multiplier plays that role.  Packing:

    p   = (a << 16) + b          # a, b int8-valued, p int32
    m   = p * w                  # ONE multiply, |m| < 2^31
    bw  = ((m + 2^15) mod 2^16) - 2^15     # signed low half  == b*w  (|b*w| <= 127^2 < 2^15)
    aw  = (m - bw) >> 16                   # borrow-corrected high == a*w

Both products are exact (tests assert bit-exactness vs two independent
integer convolutions).  The FPGA DSP's 48-bit accumulator lets the
original design accumulate *packed*; int32 lanes cannot (9 packed taps
would overflow the 16-bit guard), so we extract per-tap and accumulate
the two streams separately — multiplies stay halved (the scarce
resource), adds are cheap VPU ops.  Recorded as a hardware adaptation
in DESIGN.md.

Operand ceiling: 8 bits, as in the paper (|b*w| must fit 15 bits).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro import kernels
from repro.core.resources import (Footprint, cost_cycles, hbm_cycles,
                                  vpu_op_cycles)


def _unpack(m):
    """Recover (a*w, b*w) from m = ((a<<16)+b) * w, exactly."""
    low = ((m + (1 << 15)) & 0xFFFF) - (1 << 15)   # signed low 16 bits
    high = (m - low) >> 16
    return high, low


def _kernel(xa_ref, xb_ref, w_ref, oa_ref, ob_ref, *, kh: int, kw: int):
    ho, wo = oa_ref.shape[1], oa_ref.shape[2]
    a = xa_ref[0].astype(jnp.int32)
    b = xb_ref[0].astype(jnp.int32)
    packed = (a << 16) + b                              # (H, W, Cin)
    acc_a = jnp.zeros(oa_ref.shape[1:], jnp.int32)
    acc_b = jnp.zeros(ob_ref.shape[1:], jnp.int32)
    for i in range(kh):
        for j in range(kw):
            win = packed[i:i + ho, j:j + wo, :]          # (Ho, Wo, Cin)
            tap = w_ref[i, j].astype(jnp.int32)          # (Cin, bc)
            m = win[..., :, None] * tap[None, None, :, :]  # ONE mul / pair
            aw, bw = _unpack(m)
            acc_a = acc_a + jnp.sum(aw, axis=2)
            acc_b = acc_b + jnp.sum(bw, axis=2)
    oa_ref[0] = acc_a
    ob_ref[0] = acc_b


@functools.partial(jax.jit, static_argnames=("block_cout",))
def conv2d_ip3(xa: jnp.ndarray, xb: jnp.ndarray, w: jnp.ndarray, *,
               block_cout: int = 128):
    if xa.dtype != jnp.int8 or xb.dtype != jnp.int8 or w.dtype != jnp.int8:
        raise TypeError("Conv3 is limited to 8-bit operands (paper Table I); "
                        f"got {xa.dtype}, {xb.dtype}, {w.dtype}")
    n, h, w_, cin = xa.shape
    kh, kw, _, cout = w.shape
    ho, wo = h - kh + 1, w_ - kw + 1
    bc = min(block_cout, cout)
    grid = (n, pl.cdiv(cout, bc))
    img = pl.BlockSpec((1, h, w_, cin), lambda b, c: (b, 0, 0, 0))
    out = pl.BlockSpec((1, ho, wo, bc), lambda b, c: (b, 0, 0, c))
    return pl.pallas_call(
        functools.partial(_kernel, kh=kh, kw=kw),
        grid=grid,
        in_specs=[img, img,
                  pl.BlockSpec((kh, kw, cin, bc), lambda b, c: (0, 0, 0, c))],
        out_specs=[out, out],
        out_shape=[jax.ShapeDtypeStruct((n, ho, wo, cout), jnp.int32),
                   jax.ShapeDtypeStruct((n, ho, wo, cout), jnp.int32)],
        interpret=kernels.interpret(),
    )(xa, xb, w)


def footprint(n, h, w, cin, kh, kw, cout, *, itemsize=1,
              block_cout: int = 128) -> Footprint:
    ho, wo = h - kh + 1, w - kw + 1
    bc = min(block_cout, cout)
    vmem = (2 * h * w * cin * itemsize
            + h * w * cin * 4                 # packed plane
            + kh * kw * cin * bc * itemsize
            + 2 * ho * wo * bc * 4)
    hbm = (2 * n * h * w * cin * itemsize
           + kh * kw * cin * cout * itemsize
           + 2 * n * ho * wo * cout * 4)
    taps = n * ho * wo * cout * kh * kw * cin
    # ONE multiply per tap-pair (the win), ~5 cheap ops for unpack+acc.
    vpu = taps * 6
    return Footprint(vmem_bytes=vmem, hbm_bytes=hbm, mxu_passes=0,
                     vpu_ops=vpu,
                     est_cycles=cost_cycles(vpu_op_cycles(vpu), hbm),
                     outputs_per_pass=2, max_operand_bits=8)
