"""Pallas kernel families and the helpers every ``pallas_call`` shares.

``interpret()`` is the one place that decides how kernels run: in the
Pallas interpreter when the default backend is the CPU, compiled by
Mosaic otherwise.  No kernel takes an option for it.

``pallas_call`` wraps ``pl.pallas_call`` with that decision and with
the TPU compiler parameters: every grid axis is ``parallel`` (each grid
step writes its own output block), and the scoped-VMEM limit is raised
from the compiler's 16 MiB default to what the member's footprint says
its body holds (``vmem_limit``).

``tile_bytes`` is the VMEM a block really occupies: its last dim padded
to the 128 lanes, its second-to-last to the dtype's sublane tile.  The
footprints of the CNN members count VMEM with it.
"""
from __future__ import annotations

import math

import jax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.resources import KERNEL_VMEM_BYTES, LANE, VMEM_BYTES

# The compiler's scoped-VMEM limit when a kernel sets none.
DEFAULT_SCOPED_VMEM = 16 * 2**20
# Headroom on top of a footprint for the compiler's own temporaries
# (relayouts, spills) that no footprint itemizes.
_VMEM_SLACK = VMEM_BYTES - KERNEL_VMEM_BYTES


def interpret() -> bool:
    """True when Pallas kernels must run in the interpreter: the default
    backend is the CPU.  Tests that compile for a described TPU patch
    this function."""
    return jax.default_backend() == "cpu"


def round_up(v: int, m: int) -> int:
    return -(-int(v) // m) * m


def sublane_tile(itemsize: int) -> int:
    """Rows per (sublane, lane) tile: 8 for 32-bit, 16 for 16-bit, 32
    for 8-bit operands."""
    return 8 * max(1, 4 // int(itemsize))


def tile_bytes(shape, itemsize: int) -> int:
    """VMEM bytes of a block of ``shape`` after lane/sublane padding."""
    shape = tuple(int(d) for d in shape)
    if not shape:
        return int(itemsize)
    if len(shape) == 1:
        return round_up(shape[0], LANE) * itemsize
    lead = math.prod(shape[:-2])
    return (lead * round_up(shape[-2], sublane_tile(itemsize))
            * round_up(shape[-1], LANE) * itemsize)


def vmem_limit(vmem_bytes: int) -> int:
    """The scoped-VMEM limit a kernel asks the compiler for: its
    footprint plus headroom, never below the compiler's default and
    never above the chip's VMEM."""
    return int(min(VMEM_BYTES, max(DEFAULT_SCOPED_VMEM,
                                   vmem_bytes + _VMEM_SLACK)))


def pallas_call(kernel, *, grid, vmem_bytes: int, **kwargs):
    """``pl.pallas_call`` with the interpret decision and the TPU
    compiler parameters filled in.  ``vmem_bytes`` is the member's
    footprint VMEM for this call."""
    return pl.pallas_call(
        kernel, grid=grid, interpret=interpret(),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",) * len(grid),
            vmem_limit_bytes=vmem_limit(vmem_bytes)),
        **kwargs)
