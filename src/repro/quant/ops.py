"""Per-family quantized execution paths — every plannable compute family
(conv2d, pool2d, activation, matmul) at a planned operand width.

Each function takes float operands, quantizes them to ``bits``, runs the
family's selected kernel IP, and returns a *float* result:

* ``bits == 8`` — the true integer path: int8 codes into the kernel,
  int32 accumulation, f32 rescale (linear families carry the combined
  scale out of the accumulator; per-channel weight scales for conv and
  matmul).
* ``8 < bits < 32`` — *fake-quant*: operands are snapped to the intN
  grid but arithmetic stays float, because int32 lanes cannot accumulate
  true int16 products without overflow (the paper's FPGA DSPs had 48-bit
  accumulators; the TPU adaptation is recorded in the precision
  contract, docs/adaptive_ips.md).  Footprint pricing still credits the
  narrower operands — that is the resource the ladder trades for.

These are the building blocks ``models/blocks.py`` composes into
mixed-precision networks (where quantize/dequantize boundaries are
inserted only where adjacent sites disagree) and that the
``kernels/<family>/ops.py`` wrappers invoke when the planner lowers a
``budget=``-path call site.

``attention`` and ``ssm_scan`` have no integer kernels and are marked
``quantizable=False`` in the library — the planner never lowers them.
"""
from __future__ import annotations

from typing import Optional

import jax.numpy as jnp

from repro.quant.quantize import (QuantizedTensor, dequantize, fake_quant,
                                  quantize_acts, quantize_weights)


def _check_bits(bits: int) -> None:
    if not 2 <= bits < 32:
        raise ValueError(f"quantized execution expects a lowered width "
                         f"(2..31 bits); got {bits}")


def quantized_conv2d(x: jnp.ndarray, w: jnp.ndarray, *, bits: int = 8,
                     ip: Optional[str] = None,
                     act_scale: Optional[jnp.ndarray] = None,
                     return_scale: bool = False):
    """conv2d with operands quantized to ``bits``; f32 result.

    Weights are quantized per output channel (last axis of the
    (KH, KW, Cin, Cout) tensor); activations per-tensor, optionally at a
    calibrated ``act_scale``.

    ``return_scale=True`` returns ``(result, scale)`` instead of
    dequantizing: for the true-int8 path that is the raw int32
    accumulator plus its (1, 1, 1, Cout) scale, letting a caller fuse
    the dequantize into the next fixed-point stage
    (models/blocks.py::apply_cnn_block); fake-quant widths return
    ``(float result, None)``.
    """
    _check_bits(bits)
    from repro.kernels.conv2d.ops import conv2d
    if bits == 8:
        xq = quantize_acts(x, bits=8, scale=act_scale)
        wq = quantize_weights(w, axis=-1, bits=8)
        acc = conv2d(xq.q, wq.q, ip=ip)
        scale = xq.scale * wq.scale.reshape(1, 1, 1, -1)
        if return_scale:
            return acc, scale
        return acc.astype(jnp.float32) * scale
    y = conv2d(fake_quant(x, bits=bits), fake_quant(w, bits=bits, axis=-1),
               ip=ip)
    return (y, None) if return_scale else y


def quantized_pool2d(x: jnp.ndarray, *, window=(2, 2), stride=None,
                     mode: str = "max", bits: int = 8,
                     ip: Optional[str] = None,
                     act_scale: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """pool2d over intN codes; f32 result.

    Pooling is scale-equivariant (max exactly; avg up to the integer
    floor-division of the family contract), so the input's quantization
    scale carries straight through the pooled codes.
    """
    _check_bits(bits)
    from repro.kernels.pool2d.ops import pool2d
    if bits == 8:
        xq = quantize_acts(x, bits=8, scale=act_scale)
        y = pool2d(xq.q, window=window, stride=stride, mode=mode, ip=ip)
        return y.astype(jnp.float32) * xq.scale
    return pool2d(fake_quant(x, bits=bits), window=window, stride=stride,
                  mode=mode, ip=ip)


def quantized_activation(x: jnp.ndarray, *, kind: str = "relu",
                         bits: int = 8, ip: Optional[str] = None,
                         act_scale: Optional[jnp.ndarray] = None
                         ) -> jnp.ndarray:
    """Activation evaluated on the intN-quantized input grid; f32 result.

    The nonlinearity itself runs on dequantized values (a table over at
    most 2^bits distinct inputs); if the selected member is the LUT IP
    it re-quantizes internally to its own 256-level range — both errors
    are bounded and reported per site.
    """
    _check_bits(bits)
    from repro.kernels.activation.ops import activation
    xq = quantize_acts(x, bits=bits, scale=act_scale)
    return activation(dequantize(xq), kind=kind, ip=ip)


def quantized_fused_cnn_block(x: jnp.ndarray, w: jnp.ndarray, *,
                              pool_window=(2, 2), pool_stride=None,
                              pool_mode: str = "max",
                              activation: str = "relu", bits: int = 8,
                              ip: Optional[str] = None,
                              act_scale: Optional[jnp.ndarray] = None
                              ) -> jnp.ndarray:
    """Fused conv->pool->act with operands quantized to ``bits``; f32
    result.

    The int8 rung is the fused counterpart of the PR 3 mixed-precision
    chain: int8 codes enter the ONE launch, the int32 conv accumulator
    is rescaled by the combined (activation x per-channel weight) scale
    *in register*, and pooling + activation run on the rescaled tile —
    no intermediate fixed-point codes are materialized and the block
    performs no extra dequantize launch.  Wider lowered widths
    fake-quant the operands and run the float kernel.
    """
    _check_bits(bits)
    from repro.kernels.fused.ops import fused_cnn_block, resolve_member
    if bits == 8:
        xq = quantize_acts(x, bits=8, scale=act_scale)
        wq = quantize_weights(w, axis=-1, bits=8)
        scale = (xq.scale * wq.scale).reshape(1, 1, 1, -1)
        member = resolve_member(ip or "fused_vpu")
        return member(xq.q, wq.q, scale,
                      pool_window=tuple(pool_window),
                      pool_stride=pool_stride,
                      pool_mode=pool_mode, act_kind=activation)
    return fused_cnn_block(fake_quant(x, bits=bits),
                           fake_quant(w, bits=bits, axis=-1),
                           pool_window=pool_window, pool_stride=pool_stride,
                           pool_mode=pool_mode, activation=activation,
                           ip=ip)


def quantized_matmul(a: jnp.ndarray, b: jnp.ndarray, *, bits: int = 8,
                     ip: Optional[str] = None,
                     act_scale: Optional[jnp.ndarray] = None,
                     **tile_kwargs) -> jnp.ndarray:
    """a @ b with operands quantized to ``bits``; f32 result.

    ``b`` (the weight side) is quantized per output column; int8 runs the
    integer kernel (int32 accumulate), wider lowered widths fake-quant.
    """
    _check_bits(bits)
    from repro.kernels.matmul.ops import matmul
    if bits == 8:
        aq = quantize_acts(a, bits=8, scale=act_scale)
        bq = quantize_weights(b, axis=-1, bits=8)
        acc = matmul(aq.q, bq.q, ip=ip, **tile_kwargs)
        scale = aq.scale * bq.scale.reshape(1, -1)
        return acc.astype(jnp.float32) * scale
    return matmul(fake_quant(a, bits=bits), fake_quant(b, bits=bits, axis=-1),
                  ip=ip, **tile_kwargs)
