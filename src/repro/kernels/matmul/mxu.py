"""Tiled MXU matmul kernels — the matmul end of the IP library.

`mm_mxu` is the Conv2 analogue for the LM hot path: one MXU pass per
(bm, bn, bk) tile with a float32/int32 VMEM accumulator, K innermost so
the accumulator tile stays resident.  Works for bf16/f32 (f32 accum)
and int8 (int32 accum — the paper's fixed-point contract, and 2x MXU
throughput on TPU).

`mm_vpu` is the Conv1 analogue: no dot op at all — broadcast
multiply + reduce on the VPU.  Only sane for small/irregular shapes or
an MXU-saturated budget; exists to complete the resource spectrum.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import kernels
from repro.core.resources import (Footprint, cost_cycles, hbm_cycles,
                                  mxu_pass_cycles, vpu_op_cycles)


def _mm_kernel(a_ref, b_ref, o_ref, acc_ref, *, n_k: int, acc_dtype):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(a_ref[...], b_ref[...],
                            preferred_element_type=acc_dtype)

    @pl.when(k == n_k - 1)
    def _done():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _pad2(x, b0, b1):
    """Zero-pad a 2D array up to block multiples (exact for matmul)."""
    p0 = (-x.shape[0]) % b0
    p1 = (-x.shape[1]) % b1
    if p0 or p1:
        x = jnp.pad(x, ((0, p0), (0, p1)))
    return x


@functools.partial(jax.jit,
                   static_argnames=("bm", "bn", "bk", "out_dtype"))
def mm_mxu(a: jnp.ndarray, b: jnp.ndarray, *, bm: int = 256, bn: int = 256,
           bk: int = 512, out_dtype=None) -> jnp.ndarray:
    m, k = a.shape
    k2, n = b.shape
    assert k == k2, (a.shape, b.shape)
    integer = (jnp.issubdtype(a.dtype, jnp.integer)
               and jnp.issubdtype(b.dtype, jnp.integer))
    acc_dtype = jnp.int32 if integer else jnp.float32
    out_dtype = out_dtype or acc_dtype
    bm, bn, bk = min(bm, m), min(bn, n), min(bk, k)
    a = _pad2(a, bm, bk)
    b = _pad2(b, bk, bn)
    (mp, kp), np_ = a.shape, b.shape[1]
    n_k = pl.cdiv(kp, bk)
    grid = (pl.cdiv(mp, bm), pl.cdiv(np_, bn), n_k)
    return pl.pallas_call(
        functools.partial(_mm_kernel, n_k=n_k, acc_dtype=acc_dtype),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), acc_dtype)],
        interpret=kernels.interpret(),
    )(a, b)[:m, :n]


def _mm_vpu_kernel(a_ref, b_ref, o_ref, *, acc_dtype):
    a = a_ref[...].astype(acc_dtype)            # (bm, K)
    b = b_ref[...].astype(acc_dtype)            # (K, bn)
    # Broadcast multiply + sum: no dot — Conv1's "logic only" contract.
    o_ref[...] = jnp.sum(a[:, :, None] * b[None, :, :], axis=1).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bm", "bn"))
def mm_vpu(a: jnp.ndarray, b: jnp.ndarray, *, bm: int = 64,
           bn: int = 128) -> jnp.ndarray:
    m, k = a.shape
    _, n = b.shape
    integer = (jnp.issubdtype(a.dtype, jnp.integer)
               and jnp.issubdtype(b.dtype, jnp.integer))
    acc_dtype = jnp.int32 if integer else jnp.float32
    bm, bn = min(bm, m), min(bn, n)
    a = _pad2(a, bm, 1)
    b = _pad2(b, 1, bn)
    mp, np_ = a.shape[0], b.shape[1]
    grid = (pl.cdiv(mp, bm), pl.cdiv(np_, bn))
    return pl.pallas_call(
        functools.partial(_mm_vpu_kernel, acc_dtype=acc_dtype),
        grid=grid,
        in_specs=[pl.BlockSpec((bm, k), lambda i, j: (i, 0)),
                  pl.BlockSpec((k, bn), lambda i, j: (0, j))],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), acc_dtype),
        interpret=kernels.interpret(),
    )(a, b)[:m, :n]


def footprint_mxu(m, k, n, *, itemsize=2, bm=256, bn=256, bk=512) -> Footprint:
    bm, bn, bk = min(bm, m), min(bn, n), min(bk, k)
    vmem = bm * bk * itemsize + bk * bn * itemsize + 2 * bm * bn * 4
    hbm = (m * k + k * n) * itemsize + m * n * 4
    cyc = mxu_pass_cycles(m, k, n) * (1 if itemsize > 1 else 0.5)
    passes = pl.cdiv(m, bm) * pl.cdiv(n, bn) * pl.cdiv(k, bk)
    return Footprint(vmem_bytes=vmem, hbm_bytes=hbm, mxu_passes=passes,
                     vpu_ops=0, est_cycles=cost_cycles(cyc, hbm),
                     outputs_per_pass=1, max_operand_bits=32)


def footprint_vpu(m, k, n, *, itemsize=2, bm=64, bn=128) -> Footprint:
    bm, bn = min(bm, m), min(bn, n)
    vmem = bm * k * itemsize + k * bn * itemsize + bm * bn * 4
    hbm = (m * k + k * n) * itemsize + m * n * 4
    vpu = 2 * m * k * n
    return Footprint(vmem_bytes=vmem, hbm_bytes=hbm, mxu_passes=0,
                     vpu_ops=vpu,
                     est_cycles=cost_cycles(vpu_op_cycles(vpu), hbm),
                     outputs_per_pass=1, max_operand_bits=32)
