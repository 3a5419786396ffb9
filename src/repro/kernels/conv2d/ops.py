"""Public jit'd wrappers for the conv2d IP family.

`conv2d` / `conv2d_dual` take an explicit ``ip=`` name or a
``budget=`` (ResourceBudget) and defer to the resource-driven selector
— the paper's "automatic adaptation to the available resources".

``ladder=`` (e.g. ``(16, 8)``) lets the planner lower this call's
operand width when it cannot fit at native precision; a lowered plan
executes transparently through the quantized path
(``repro.quant.ops.quantized_conv2d``) and still returns float.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax.numpy as jnp

from repro.core.resources import ResourceBudget
from repro.kernels.conv2d.ip1_vpu import conv2d_ip1
from repro.kernels.conv2d.ip2_mxu import conv2d_ip2
from repro.kernels.conv2d.ip3_packed import conv2d_ip3
from repro.kernels.conv2d.ip4_dual import conv2d_ip4

_SINGLE = {"ip1_vpu": conv2d_ip1, "ip2_mxu": conv2d_ip2}
_DUAL = {"ip3_packed": conv2d_ip3, "ip4_dual": conv2d_ip4}


def _maybe_reduce(y: jnp.ndarray, reduce_axis: Optional[str],
                  reduce: str) -> jnp.ndarray:
    """The channel-split hook: inside ``shard_map``, a conv whose input
    channels are sharded produces a *partial* sum — summing the partials
    over the mesh axis makes it the full output on every device.
    ``reduce="psum"`` is the XLA reference; ``"ring"`` goes through the
    explicit ppermute ring (``distributed/collectives.py``)."""
    if reduce_axis is None:
        return y
    if reduce == "ring":
        from repro.distributed.collectives import ring_all_reduce
        return ring_all_reduce(y, reduce_axis)
    if reduce != "psum":
        raise ValueError(f"unknown reduce {reduce!r}; have ('psum', 'ring')")
    import jax
    return jax.lax.psum(y, reduce_axis)


def conv2d(x: jnp.ndarray, w: jnp.ndarray, *, ip: Optional[str] = None,
           budget: Optional[ResourceBudget] = None, ladder=(),
           reduce_axis: Optional[str] = None,
           reduce: str = "psum", stride: int = 1, padding: int = 0,
           **tile_kwargs) -> jnp.ndarray:
    """Single-stream convolution through a selected IP (Conv1/Conv2).

    ``stride`` and zero ``padding`` (on every side) reach the member
    as they are; the quantized path runs only the VALID stride-1 conv.

    ``tile_kwargs`` forward tiling parameters to the member (e.g.
    ``block_cout=`` for ``ip2_mxu``, typically from
    ``core.autotune.plan_tile_overrides``); pass them only with an
    explicit ``ip=`` or a plan known to pick a member that accepts them.

    ``reduce_axis=`` is the mesh-sharded execution hook: under
    ``shard_map`` with input channels split across that named axis, each
    device's result is a partial sum and this call all-reduces it into
    the full output (``reduce=`` picks ``"psum"`` or the explicit
    ``"ring"`` path; see distributed/shard_exec.py).
    """
    if ip is None:
        from repro.core.ip import SiteSpec
        from repro.core.plan import plan_single
        knobs = ({"stride": stride, "padding": padding}
                 if (stride, padding) != (1, 0) else {})
        spec = SiteSpec.make("conv2d", "conv2d", (x.shape, w.shape),
                             x.dtype, ladder=ladder, dual=False, **knobs)
        planned = plan_single(spec, budget)
        if planned.lowered:
            if knobs:
                raise ValueError("the quantized conv runs only VALID "
                                 "stride-1 convs; plan a strided or "
                                 "padded conv without a ladder")
            from repro.quant.ops import quantized_conv2d
            y = quantized_conv2d(x, w, bits=planned.precision_bits,
                                 ip=planned.ip.name)
            return _maybe_reduce(y, reduce_axis, reduce)
        ip = planned.ip.name
    ip = ip.split(".")[-1]
    if ip not in _SINGLE:
        raise KeyError(f"{ip!r} is not a single-stream conv IP "
                       f"(have {sorted(_SINGLE)})")
    if (stride, padding) != (1, 0):
        tile_kwargs = dict(tile_kwargs, stride=stride, padding=padding)
    y = _SINGLE[ip](x, w, **tile_kwargs)
    return _maybe_reduce(y, reduce_axis, reduce)


def conv2d_dual(xa: jnp.ndarray, xb: jnp.ndarray, w: jnp.ndarray, *,
                ip: Optional[str] = None,
                budget: Optional[ResourceBudget] = None
                ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Two parallel convolutions through a selected IP (Conv3/Conv4).

    No ``ladder=``: dual-stream callers already commit to a concrete
    operand dtype per stream (Conv3 demands int8 inputs outright).
    """
    if ip is None:
        from repro.core.ip import SiteSpec
        from repro.core.plan import plan_single
        spec = SiteSpec.make("conv2d", "conv2d", (xa.shape, w.shape),
                             xa.dtype, dual=True)
        ip = plan_single(spec, budget).ip.name
    ip = ip.split(".")[-1]
    if ip not in _DUAL:
        raise KeyError(f"{ip!r} is not a dual-stream conv IP "
                       f"(have {sorted(_DUAL)})")
    return _DUAL[ip](xa, xb, w)
