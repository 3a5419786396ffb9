"""Flash-decode — the attention IP's serving member: one new token
against a long KV cache.

The q "tile" is the whole GQA group of a kv head (group x d), which
puts the group in the sublane dimension — the TPU-native layout for
single-token decode (a (1, d) q tile would waste 7/8 sublanes).
Grid: (B * Hkv, Skv / bk); online max/sum merge across kv blocks in
VMEM scratch — the same partial-softmax merge the SP (sequence-
parallel) path uses across chips with psum (distributed/collectives).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import kernels
from repro.core.resources import Footprint, hbm_cycles

_NEG_INF = -1e30


def _decode_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                   n_kv: int, scale: float, bk: int, skv: int):
    kv = pl.program_id(1)

    @pl.when(kv == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32) * scale        # (group, d)
    k = k_ref[0].astype(jnp.float32)                # (bk, d)
    v = v_ref[0].astype(jnp.float32)
    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32)  # (group, bk)
    group = s.shape[0]
    k_pos = kv * bk + jax.lax.broadcasted_iota(jnp.int32, (group, bk), 1)
    s = jnp.where(k_pos < skv, s, _NEG_INF)
    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, s.max(axis=-1))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new[:, None])
    l_ref[...] = l_ref[...] * alpha + p.sum(axis=-1)
    acc_ref[...] = (acc_ref[...] * alpha[:, None]
                    + jnp.dot(p, v, preferred_element_type=jnp.float32))
    m_ref[...] = m_new

    @pl.when(kv == n_kv - 1)
    def _done():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bk",))
def flash_decode(q, k, v, *, bk: int = 1024):
    """q: (B, Hq, 1, D); k/v: (B, Hkv, Skv, D) -> (B, Hq, 1, D)."""
    b, hq, sq, d = q.shape
    assert sq == 1, "flash_decode is the single-token member"
    _, hkv, skv, _ = k.shape
    group = hq // hkv
    scale = d ** -0.5
    bk = min(bk, skv)
    pk = (-skv) % bk
    if pk:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pk), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pk), (0, 0)))
    skvp = skv + pk
    qr = q.reshape(b, hkv, group, d).reshape(b * hkv, group, d)
    kr = k.reshape(b * hkv, skvp, d)
    vr = v.reshape(b * hkv, skvp, d)
    n_kv = pl.cdiv(skvp, bk)
    grid = (b * hkv, n_kv)
    out = pl.pallas_call(
        functools.partial(_decode_kernel, n_kv=n_kv, scale=scale, bk=bk,
                          skv=skv),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, group, d), lambda h, kv: (h, 0, 0)),
            pl.BlockSpec((1, bk, d), lambda h, kv: (h, kv, 0)),
            pl.BlockSpec((1, bk, d), lambda h, kv: (h, kv, 0)),
        ],
        out_specs=pl.BlockSpec((1, group, d), lambda h, kv: (h, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b * hkv, group, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((group,), jnp.float32),
                        pltpu.VMEM((group,), jnp.float32),
                        pltpu.VMEM((group, d), jnp.float32)],
        interpret=kernels.interpret(),
    )(qr, kr, vr)
    return out.reshape(b, hq, 1, d)


def footprint(b, hq, hkv, skv, d, *, itemsize=2, bk=1024) -> Footprint:
    group = hq // hkv
    bk_ = min(bk, skv)
    vmem = (group * d + 2 * bk_ * d) * itemsize + (group * d + 2 * group) * 4
    hbm = 2 * b * hkv * skv * d * itemsize + 2 * b * hq * d * itemsize
    # decode is HBM-bound by construction: est = cache sweep time.
    return Footprint(vmem_bytes=int(vmem), hbm_bytes=int(hbm),
                     mxu_passes=b * hkv * pl.cdiv(skv, bk_),
                     vpu_ops=int(4 * b * hq * skv),
                     est_cycles=hbm_cycles(hbm),
                     outputs_per_pass=1, max_operand_bits=32)
