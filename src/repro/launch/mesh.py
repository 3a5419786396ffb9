"""Production mesh builders.

Functions, not module constants — importing this module never touches
jax device state (the dry-run sets XLA_FLAGS before first jax init).

Every mesh, and every sharding ``distributed/sharding.to_shardings``
builds, has ``Auto`` axes: the model code places arrays with sharding
constraints and leaves the propagation to the compiler, which
``jax.make_mesh``'s ``Explicit`` default would turn into per-op
sharding-type errors (e.g. the embedding gather on a table sharded over
``model``).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def auto_axes(mesh):
    """``mesh`` with every axis ``Auto`` (same devices, same names)."""
    return jax.sharding.Mesh(mesh.devices, mesh.axis_names,
                             axis_types=(AxisType.Auto,) * mesh.devices.ndim)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return auto_axes(jax.make_mesh(shape, axes))


def make_host_mesh(data: int = 1, model: int = 1):
    """Small mesh over whatever devices exist (tests / examples)."""
    n = len(jax.devices())
    data = min(data, n)
    model = min(model, max(n // data, 1))
    return auto_axes(jax.make_mesh((data, model), ("data", "model")))


def mesh_axis_sizes(mesh) -> dict:
    return dict(zip(mesh.axis_names, mesh.devices.shape))


def dp_axes(mesh):
    """Data-parallel axes: ('pod','data') on multi-pod, ('data',) else."""
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)
