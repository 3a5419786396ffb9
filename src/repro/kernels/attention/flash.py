"""Flash attention (tiled online-softmax) — the attention IP's MXU-heavy
member for training/prefill.

Adaptation notes (FPGA -> TPU): the paper's BlockSpec-era insight —
"size the working set to on-chip memory, stream the rest" — is exactly
flash attention's game: q/k/v tiles sized to VMEM, softmax statistics
(running max m, normalizer l) live in VMEM scratch across the kv-block
grid dimension, HBM traffic stays O(S*D) instead of O(S^2).

Grid: (B*Hq, Sq/bq, Skv/bk), kv innermost.  GQA is handled in the
index_map (q head -> kv head).  Causal blocks above the diagonal are
skipped with pl.when (no MXU work scheduled).
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

_NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  n_kv: int, bq: int, bk: int, causal: bool, offs: int,
                  scale: float, skv: int):
    kv = pl.program_id(2)

    @pl.when(kv == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    qi = pl.program_id(1)

    def _body():
        q = q_ref[0].astype(jnp.float32) * scale            # (bq, d)
        k = k_ref[0].astype(jnp.float32)                    # (bk, d)
        v = v_ref[0].astype(jnp.float32)
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32)  # (bq, bk)
        k_pos = kv * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = k_pos < skv                                  # kv padding
        if causal:
            q_pos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            mask = mask & (k_pos <= q_pos + offs)
        s = jnp.where(mask, s, _NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=-1))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, None])
        l_ref[...] = l_ref[...] * alpha + p.sum(axis=-1)
        acc_ref[...] = (acc_ref[...] * alpha[:, None]
                        + jnp.dot(p, v, preferred_element_type=jnp.float32))
        m_ref[...] = m_new

    if causal:
        # Skip fully-masked blocks: first kv index of block > last visible.
        @pl.when(kv * bk <= qi * bq + (bq - 1) + offs)
        def _run():
            _body()
    else:
        _body()

    @pl.when(kv == n_kv - 1)
    def _done():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "bq", "bk"))
def flash_attention(q, k, v, *, causal: bool = True, bq: int = 512,
                    bk: int = 512):
    b, hq, sq, d = q.shape
    _, hkv, skv, _ = k.shape
    group = hq // hkv
    scale = d ** -0.5
    bq = min(bq, sq)
    bk = min(bk, skv)
    offs = skv - sq
    pq = (-sq) % bq
    pk = (-skv) % bk
    if pq:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pq), (0, 0)))
    if pk:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pk), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pk), (0, 0)))
    sqp, skvp = sq + pq, skv + pk
    qr = q.reshape(b * hq, sqp, d)
    kr = k.reshape(b * hkv, skvp, d)
    vr = v.reshape(b * hkv, skvp, d)
    n_kv = pl.cdiv(skvp, bk)
    grid = (b * hq, pl.cdiv(sqp, bq), n_kv)

    def kv_map(h, i, kv):
        return (h // group, kv, 0)

    out = pl.pallas_call(
        functools.partial(_flash_kernel, n_kv=n_kv, bq=bq, bk=bk,
                          causal=causal, offs=offs, scale=scale, skv=skv),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda h, i, kv: (h, i, 0)),
            pl.BlockSpec((1, bk, d), kv_map),
            pl.BlockSpec((1, bk, d), kv_map),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda h, i, kv: (h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b * hq, sqp, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq,), jnp.float32),
                        pltpu.VMEM((bq,), jnp.float32),
                        pltpu.VMEM((bq, d), jnp.float32)],
        interpret=kernels.interpret(),
    )(qr, kr, vr)
    return out.reshape(b, hq, sqp, d)[:, :, :sq, :]


def footprint(b, hq, hkv, sq, skv, d, *, itemsize=2, bq=512, bk=512,
              causal=True) -> Footprint:
    bq_, bk_ = min(bq, sq), min(bk, skv)
    vmem = (bq_ * d + 2 * bk_ * d) * itemsize + (bq_ * d + 2 * bq_) * 4
    hbm = (b * hq * sq * d * 2 + 2 * b * hkv * skv * d) * itemsize
    frac = 0.5 if causal and sq == skv else 1.0
    flops = 4.0 * b * hq * sq * skv * d * frac
    cyc = flops / 2 / (128 * 128)  # MXU MACs/cycle
    passes = int(b * hq * pl.cdiv(sq, bq_) * pl.cdiv(skv, bk_) * frac) + 1
    return Footprint(vmem_bytes=int(vmem), hbm_bytes=int(hbm),
                     mxu_passes=passes, vpu_ops=int(b * hq * sq * skv * frac * 4),
                     est_cycles=cost_cycles(cyc, hbm),
                     outputs_per_pass=1, max_operand_bits=32)
