"""Dual-stream matmul kernels — Conv3/Conv4 generalized to the LM hot path.

`mm_dual_shared` (Conv3 analogue): two int8 activation streams share one
weight-tile fetch and one kernel pass — the weights cross HBM->VMEM
*once* for two outputs (the paper's serial-coefficient-load economy) and
the int8 MXU path runs at 2x bf16 throughput ("two convolutions per
DSP").  Operands limited to 8 bits, as in the paper.

`mm_dual_full` (Conv4 analogue): same shared-weight structure at full
precision (bf16/f32) — two MXU pass groups, wider operands.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import kernels
from repro.core.resources import (Footprint, cost_cycles, hbm_cycles,
                                  mxu_pass_cycles)


def _dual_kernel(a1_ref, a2_ref, b_ref, o1_ref, o2_ref, acc1, acc2, *,
                 n_k: int, acc_dtype):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc1[...] = jnp.zeros_like(acc1)
        acc2[...] = jnp.zeros_like(acc2)

    b = b_ref[...]                    # ONE weight-tile load ...
    acc1[...] += jnp.dot(a1_ref[...], b, preferred_element_type=acc_dtype)
    acc2[...] += jnp.dot(a2_ref[...], b, preferred_element_type=acc_dtype)

    @pl.when(k == n_k - 1)
    def _done():
        o1_ref[...] = acc1[...].astype(o1_ref.dtype)
        o2_ref[...] = acc2[...].astype(o2_ref.dtype)


def _mm_dual(a1, a2, b, *, bm, bn, bk, require_int8):
    m, k = a1.shape
    assert a1.shape == a2.shape
    _, n = b.shape
    if require_int8:
        for t in (a1, a2, b):
            if t.dtype != jnp.int8:
                raise TypeError("mm_dual_shared is limited to 8-bit operands "
                                f"(paper Conv3 contract); got {t.dtype}")
    integer = jnp.issubdtype(a1.dtype, jnp.integer)
    acc_dtype = jnp.int32 if integer else jnp.float32
    bm, bn, bk = min(bm, m), min(bn, n), min(bk, k)
    from repro.kernels.matmul.mxu import _pad2
    a1 = _pad2(a1, bm, bk)
    a2 = _pad2(a2, bm, bk)
    b = _pad2(b, bk, bn)
    (mp, kp), np_ = a1.shape, b.shape[1]
    n_k = pl.cdiv(kp, bk)
    grid = (pl.cdiv(mp, bm), pl.cdiv(np_, bn), n_k)
    a_spec = pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk))
    o_spec = pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j))
    out = pl.pallas_call(
        functools.partial(_dual_kernel, n_k=n_k, acc_dtype=acc_dtype),
        grid=grid,
        in_specs=[a_spec, a_spec,
                  pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j))],
        out_specs=[o_spec, o_spec],
        out_shape=[jax.ShapeDtypeStruct((mp, np_), acc_dtype)] * 2,
        scratch_shapes=[pltpu.VMEM((bm, bn), acc_dtype)] * 2,
        interpret=kernels.interpret(),
    )(a1, a2, b)
    return tuple(o[:m, :n] for o in out)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk"))
def mm_dual_shared(a1, a2, b, *, bm: int = 256, bn: int = 256, bk: int = 512):
    return _mm_dual(a1, a2, b, bm=bm, bn=bn, bk=bk, require_int8=True)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk"))
def mm_dual_full(a1, a2, b, *, bm: int = 256, bn: int = 256, bk: int = 512):
    return _mm_dual(a1, a2, b, bm=bm, bn=bn, bk=bk, require_int8=False)


def footprint_dual(m, k, n, *, itemsize=1, bm=256, bn=256, bk=512,
                   int8: bool = True) -> Footprint:
    bm, bn, bk = min(bm, m), min(bn, n), min(bk, k)
    vmem = 2 * bm * bk * itemsize + bk * bn * itemsize + 4 * bm * bn * 4
    hbm = 2 * m * k * itemsize + k * n * itemsize + 2 * m * n * 4
    # int8 MXU runs 2x: two streams cost one bf16-equivalent pass set.
    scale = 1.0 if int8 else 2.0
    cyc = scale * mxu_pass_cycles(m, k, n)
    passes = int(scale * pl.cdiv(m, bm) * pl.cdiv(n, bn) * pl.cdiv(k, bk))
    return Footprint(vmem_bytes=vmem, hbm_bytes=hbm, mxu_passes=max(passes, 1),
                     vpu_ops=0, est_cycles=cost_cycles(cyc, hbm),
                     outputs_per_pass=2,
                     max_operand_bits=8 if int8 else 32)
