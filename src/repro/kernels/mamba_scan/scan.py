"""Pallas selective-scan kernel — the SSM recurrence with the state
resident in VMEM.

The jnp `lax.scan` twin round-trips the (Di x Ds) state through HBM
every timestep (T x Di x Ds x 4 B each way); here the state lives in a
VMEM scratch for the whole time block and only x/dt/B/C stream in and
y streams out — HBM traffic drops from O(T·Di·Ds) to O(T·(Di + Ds)),
a (Ds= d_state)-fold cut of the recurrence's memory term.  This is the
Conv1-style "logic-only" end of the IP spectrum (no MXU; the per-step
update is rank-1 VPU work), matching DESIGN.md §Arch-applicability for
the attention-free blocks.

Grid: (B, Di/bdi).  Block: full T in VMEM (T·bdi·4 bytes — e.g.
4096x256 = 4 MiB), state scratch (bdi, Ds).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import kernels
from repro.core.resources import (Footprint, cost_cycles, hbm_cycles,
                                  vpu_op_cycles)


def _kernel(x_ref, dt_ref, b_ref, c_ref, a_ref, y_ref, hout_ref, h_ref, *,
            T: int):
    h_ref[...] = jnp.zeros_like(h_ref)

    def step(t, _):
        tsl = (slice(None), pl.ds(t, 1), slice(None))
        x_t = x_ref[tsl][0, 0]             # (bdi,)
        dt_t = dt_ref[tsl][0, 0]
        b_t = b_ref[tsl][0, 0]             # (Ds,)
        c_t = c_ref[tsl][0, 0]
        dA = jnp.exp(dt_t[:, None] * a_ref[...])                     # (bdi,Ds)
        dBx = (dt_t * x_t)[:, None] * b_t[None, :]
        h_ref[...] = dA * h_ref[...] + dBx
        y_t = jnp.sum(h_ref[...] * c_t[None, :], axis=1)             # (bdi,)
        y_ref[tsl] = y_t[None, None]
        return 0

    jax.lax.fori_loop(0, T, step, 0)
    hout_ref[...] = h_ref[...][None]


@functools.partial(jax.jit, static_argnames=("block_di",))
def selective_scan(x, dt, bp, cp, a, *, block_di: int = 256):
    """x/dt: (B,T,Di); bp/cp: (B,T,Ds); a: (Di,Ds) -> (y (B,T,Di), h)."""
    B, T, Di = x.shape
    Ds = a.shape[1]
    bdi = min(block_di, Di)
    grid = (B, pl.cdiv(Di, bdi))
    f32 = lambda t: t.astype(jnp.float32)
    y, h = pl.pallas_call(
        functools.partial(_kernel, T=T),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, T, bdi), lambda b, d: (b, 0, d)),
            pl.BlockSpec((1, T, bdi), lambda b, d: (b, 0, d)),
            pl.BlockSpec((1, T, Ds), lambda b, d: (b, 0, 0)),
            pl.BlockSpec((1, T, Ds), lambda b, d: (b, 0, 0)),
            pl.BlockSpec((bdi, Ds), lambda b, d: (d, 0)),
        ],
        out_specs=[pl.BlockSpec((1, T, bdi), lambda b, d: (b, 0, d)),
                   pl.BlockSpec((1, bdi, Ds), lambda b, d: (b, d, 0))],
        out_shape=[jax.ShapeDtypeStruct((B, T, Di), jnp.float32),
                   jax.ShapeDtypeStruct((B, Di, Ds), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((bdi, Ds), jnp.float32)],
        interpret=kernels.interpret(),
    )(f32(x), f32(dt), f32(bp), f32(cp), f32(a))
    return y, h


def footprint(b, t, di, ds, *, block_di: int = 256) -> Footprint:
    bdi = min(block_di, di)
    vmem = (2 * t * bdi + 2 * t * ds + bdi * ds * 2 + t * bdi) * 4
    hbm = (2 * b * t * di + 2 * b * t * ds + di * ds
           + b * t * di + b * di * ds) * 4
    vpu = b * t * di * ds * 6       # dA, dBx, h update, y reduce
    return Footprint(vmem_bytes=int(vmem), hbm_bytes=int(hbm), mxu_passes=0,
                     vpu_ops=int(vpu),
                     est_cycles=cost_cycles(vpu_op_cycles(vpu), hbm),
                     outputs_per_pass=1, max_operand_bits=32)
