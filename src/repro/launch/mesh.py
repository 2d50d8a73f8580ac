"""Production mesh construction (function, not module-level constant — importing
this module never touches jax device state)."""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: 16x16 = 256 chips ("data", "model").
    Multi-pod: 2 pods x 256 = 512 chips ("pod", "data", "model")."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape: tuple, axes: tuple):
    """Arbitrary mesh (tests / smoke runs / examples), every axis Auto: the
    step builders place data with explicit shardings, and ``jax.make_mesh``
    would otherwise make the axes Explicit."""
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def worker_axes(mesh) -> tuple:
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def pod_axis(mesh) -> str | None:
    return "pod" if "pod" in mesh.axis_names else None


def num_workers(mesh) -> int:
    n = 1
    for a in worker_axes(mesh):
        n *= mesh.shape[a]
    return n
