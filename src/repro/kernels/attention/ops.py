"""Public wrappers for the attention IP family (selector-aware).

Attention carries no ``ladder=``: the family is registered
``quantizable=False`` (no integer kernels), so the planner always holds
its sites at native width.
"""
from __future__ import annotations

from typing import Optional

import jax.numpy as jnp

from repro.core.resources import ResourceBudget
from repro.kernels.attention.flash import flash_attention
from repro.kernels.attention.decode import flash_decode
from repro.kernels.attention.ref import attention_ref


def attention(q, k, v, *, causal: bool = True, ip: Optional[str] = None,
              budget: Optional[ResourceBudget] = None):
    if ip is None:
        from repro.core.ip import SiteSpec
        from repro.core.plan import plan_single
        spec = SiteSpec.make("attention", "attention", (q.shape, k.shape),
                             q.dtype)
        ip = plan_single(spec, budget).ip.name
    ip = ip.split(".")[-1]
    if ip == "attn_flash":
        return flash_attention(q, k, v, causal=causal)
    if ip == "attn_decode":
        return flash_decode(q, k, v)
    if ip == "attn_naive":
        return attention_ref(q, k, v, causal=causal)
    raise KeyError(f"unknown attention IP {ip!r}")
