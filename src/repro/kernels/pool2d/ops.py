"""Public jit'd wrappers for the pool2d IP family.

`pool2d` takes an explicit ``ip=`` name or a ``budget=``
(ResourceBudget) and defers to the resource-driven selector, mirroring
`kernels/conv2d/ops.py`.  ``ladder=`` allows the planner to lower the
call's operand width; lowered plans execute through
``repro.quant.ops.quantized_pool2d`` and return float.
"""
from __future__ import annotations

from typing import Optional

import jax.numpy as jnp

from repro.core.resources import ResourceBudget
from repro.kernels.pool2d.mxu_im2col import pool2d_im2col
from repro.kernels.pool2d.ref import check_pool_geometry
from repro.kernels.pool2d.vpu_window import pool2d_window

_MEMBERS = {"pool_vpu": pool2d_window, "pool_im2col": pool2d_im2col}


def pool2d(x: jnp.ndarray, *, window=(2, 2), stride=None, mode: str = "max",
           ip: Optional[str] = None,
           budget: Optional[ResourceBudget] = None, ladder=()) -> jnp.ndarray:
    """Max/avg pooling through a selected IP (Pool1/Pool2)."""
    if mode not in ("max", "avg"):
        raise ValueError(f"unknown pool mode {mode!r}; have ('max', 'avg')")
    window, stride = check_pool_geometry(x.shape, window, stride)
    if ip is None:
        from repro.core.ip import SiteSpec
        from repro.core.plan import plan_single
        spec = SiteSpec.make("pool2d", "pool2d", (x.shape,), x.dtype,
                             ladder=ladder, window=window, stride=stride,
                             mode=mode)
        planned = plan_single(spec, budget)
        if planned.lowered:
            from repro.quant.ops import quantized_pool2d
            return quantized_pool2d(x, window=window, stride=stride,
                                    mode=mode, bits=planned.precision_bits,
                                    ip=planned.ip.name)
        ip = planned.ip.name
    ip = ip.split(".")[-1]
    if ip not in _MEMBERS:
        raise KeyError(f"{ip!r} is not a pool2d IP (have {sorted(_MEMBERS)})")
    return _MEMBERS[ip](x, window=window, stride=stride, mode=mode)
