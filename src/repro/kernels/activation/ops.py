"""Public jit'd wrappers for the activation IP family.

`activation` takes an explicit ``ip=`` name or a ``budget=``
(ResourceBudget) and defers to the resource-driven selector, mirroring
`kernels/conv2d/ops.py`.  ``ladder=`` allows the planner to lower the
call's operand width; lowered plans evaluate the nonlinearity on the
intN-quantized input grid (``repro.quant.ops.quantized_activation``).
"""
from __future__ import annotations

from typing import Optional

import jax.numpy as jnp

from repro.core.resources import ResourceBudget
from repro.kernels.activation.lut_poly import activation_lut
from repro.kernels.activation.vpu_exact import activation_exact

_MEMBERS = {"act_vpu": activation_exact, "act_lut": activation_lut}


def activation(x: jnp.ndarray, *, kind: str = "relu",
               ip: Optional[str] = None,
               budget: Optional[ResourceBudget] = None,
               ladder=()) -> jnp.ndarray:
    """Elementwise activation through a selected IP (Act1/Act2)."""
    if ip is None:
        from repro.core.ip import SiteSpec
        from repro.core.plan import plan_single
        spec = SiteSpec.make("activation", "activation", (x.shape,),
                             x.dtype, ladder=ladder, kind=kind)
        planned = plan_single(spec, budget)
        if planned.lowered:
            from repro.quant.ops import quantized_activation
            return quantized_activation(x, kind=kind,
                                        bits=planned.precision_bits,
                                        ip=planned.ip.name)
        ip = planned.ip.name
    ip = ip.split(".")[-1]
    if ip not in _MEMBERS:
        raise KeyError(
            f"{ip!r} is not an activation IP (have {sorted(_MEMBERS)})")
    return _MEMBERS[ip](x, kind=kind)
