"""Pure-jnp oracle for the conv2d IP family.

Contract shared by all four IPs:
  x : (N, H, W, Cin)            activations (int8 fixed-point or float)
  w : (KH, KW, Cin, Cout)       kernel coefficients
  y : (N, H-KH+1, W-KW+1, Cout) VALID padding, stride 1

The single-stream members also take ``stride`` and zero ``padding`` on
every side: y is then (N, (H+2P-KH)//S+1, (W+2P-KW)//S+1, Cout).

Integer inputs accumulate exactly in int32 (the paper's fixed-point
contract); float inputs accumulate in float32 at full precision (on a
TPU the default would be one bf16 MXU pass, ~1e-3 relative error).
"""
from __future__ import annotations

import jax.numpy as jnp
from jax import lax


def _acc_dtype(x_dtype, w_dtype):
    if jnp.issubdtype(x_dtype, jnp.integer) and jnp.issubdtype(w_dtype, jnp.integer):
        return jnp.int32
    return jnp.float32


def conv2d_ref(x: jnp.ndarray, w: jnp.ndarray, *, stride: int = 1,
               padding: int = 0) -> jnp.ndarray:
    """Reference convolution (cross-correlation, as in CNN frameworks)."""
    acc = _acc_dtype(x.dtype, w.dtype)
    out = lax.conv_general_dilated(
        x.astype(acc), w.astype(acc),
        window_strides=(stride, stride),
        padding=((padding, padding), (padding, padding)),
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=None if acc == jnp.int32 else lax.Precision.HIGHEST,
        preferred_element_type=acc)
    return out


def conv2d_dual_ref(xa: jnp.ndarray, xb: jnp.ndarray, w: jnp.ndarray):
    """Two parallel convolutions sharing one kernel (Conv3/Conv4 contract)."""
    return conv2d_ref(xa, w), conv2d_ref(xb, w)
