"""Public wrappers for the matmul IP family (selector-aware).

``ladder=`` on `matmul` lets the planner lower the call's operand width
(w8a8 through the int8 MXU path) when the native width does not fit;
lowered plans execute via ``repro.quant.ops.quantized_matmul``.
"""
from __future__ import annotations

from typing import Optional

import jax.numpy as jnp

from repro.core.resources import ResourceBudget
from repro.kernels.matmul.mxu import mm_mxu, mm_vpu
from repro.kernels.matmul.dual import mm_dual_full, mm_dual_shared

_SINGLE = {"mm_mxu": mm_mxu, "mm_vpu": mm_vpu}
_DUAL = {"mm_dual_shared": mm_dual_shared, "mm_dual_full": mm_dual_full}


def matmul(a: jnp.ndarray, b: jnp.ndarray, *, ip: Optional[str] = None,
           budget: Optional[ResourceBudget] = None, ladder=(),
           **tile_kwargs) -> jnp.ndarray:
    if ip is None:
        from repro.core.ip import SiteSpec
        from repro.core.plan import plan_single
        spec = SiteSpec.make("matmul", "matmul", (a.shape, b.shape),
                             a.dtype, ladder=ladder, dual=False)
        planned = plan_single(spec, budget)
        if planned.lowered:
            from repro.quant.ops import quantized_matmul
            return quantized_matmul(a, b, bits=planned.precision_bits,
                                    ip=planned.ip.name, **tile_kwargs)
        ip = planned.ip.name
    ip = ip.split(".")[-1]
    return _SINGLE[ip](a, b, **tile_kwargs)


def matmul_dual(a1: jnp.ndarray, a2: jnp.ndarray, b: jnp.ndarray, *,
                ip: Optional[str] = None,
                budget: Optional[ResourceBudget] = None,
                **tile_kwargs):
    if ip is None:
        from repro.core.ip import SiteSpec
        from repro.core.plan import plan_single
        spec = SiteSpec.make("matmul", "matmul", (a1.shape, b.shape),
                             a1.dtype, dual=True)
        ip = plan_single(spec, budget).ip.name
    ip = ip.split(".")[-1]
    return _DUAL[ip](a1, a2, b, **tile_kwargs)
