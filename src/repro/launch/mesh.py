"""Production mesh construction.

A FUNCTION, not a module-level constant: importing this module must never
touch jax device state (the dry-run forces 512 host devices; smoke tests and
benches must keep seeing 1).
"""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 chips per pod ("data" x "model"); two pods add a "pod" axis."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape, axes):
    """Arbitrary mesh (tests use small ones, e.g. (2, 4))."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def mesh_context(mesh):
    """``with <mesh active>`` context."""
    return jax.set_mesh(mesh)


def data_axes(mesh) -> tuple[str, ...]:
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def model_axis(mesh) -> str:
    return "model"


def mesh_dims(mesh) -> dict:
    return dict(zip(mesh.axis_names, mesh.devices.shape))
