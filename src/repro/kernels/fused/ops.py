"""Public jit'd wrapper for the fused CNN-block IP family.

`fused_cnn_block` takes an explicit ``ip=`` name or a ``budget=``
(ResourceBudget) and defers to the resource-driven selector, mirroring
`kernels/conv2d/ops.py`.  ``ladder=`` lets the planner lower the whole
fused block's operand width; a lowered plan executes through
``repro.quant.ops.quantized_fused_cnn_block`` (int8: integer kernel with
the in-register rescale) and still returns float.
"""
from __future__ import annotations

from typing import Optional

import jax.numpy as jnp

from repro.core.resources import ResourceBudget
from repro.kernels.fused.cnn_block import fused_cnn_mxu, fused_cnn_vpu

_MEMBERS = {"fused_vpu": fused_cnn_vpu, "fused_mxu": fused_cnn_mxu}


def resolve_member(ip: str):
    """Qualified-or-short member name -> kernel, with the family-standard
    error (shared by the float wrapper below and the quantized path)."""
    short = ip.split(".")[-1]
    if short not in _MEMBERS:
        raise KeyError(f"{short!r} is not a fused CNN-block IP "
                       f"(have {sorted(_MEMBERS)})")
    return _MEMBERS[short]


def fused_cnn_block(x: jnp.ndarray, w: jnp.ndarray, *,
                    pool_window=(2, 2), pool_stride=None,
                    pool_mode: str = "max", activation: str = "relu",
                    ip: Optional[str] = None,
                    budget: Optional[ResourceBudget] = None, ladder=(),
                    **tile_kwargs) -> jnp.ndarray:
    """conv -> pool -> activation as ONE launch through a selected member.

    ``tile_kwargs`` forward tiling parameters (``block_cout=``, typically
    from ``core.autotune.plan_tile_overrides``).
    """
    if ip is None:
        from repro.core.ip import SiteSpec
        from repro.core.plan import plan_single
        spec = SiteSpec.make("cnn_fused", "cnn_fused", (x.shape, w.shape),
                             x.dtype, ladder=ladder, window=pool_window,
                             stride=pool_stride, mode=pool_mode,
                             kind=activation)
        planned = plan_single(spec, budget)
        if planned.lowered:
            from repro.quant.ops import quantized_fused_cnn_block
            return quantized_fused_cnn_block(
                x, w, pool_window=pool_window, pool_stride=pool_stride,
                pool_mode=pool_mode, activation=activation,
                bits=planned.precision_bits, ip=planned.ip.name)
        ip = planned.ip.name
    return resolve_member(ip)(x, w, pool_window=tuple(pool_window),
                              pool_stride=pool_stride, pool_mode=pool_mode,
                              act_kind=activation, **tile_kwargs)
