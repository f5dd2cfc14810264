"""Production mesh construction (DESIGN.md §5).

A function, not a module-level constant, so importing this module never
touches jax device state.
"""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    """TPU v5e pod: 16x16 = 256 chips; multi-pod: 2 pods = 512 chips."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return auto_mesh(shape, axes)


def make_host_mesh():
    """Single-device mesh for CPU smoke paths."""
    return auto_mesh((1, 1), ("data", "model"))


def auto_mesh(shape, axes):
    """``jax.make_mesh`` with ``Auto`` axes: the sharding policy pins
    intermediates with ``with_sharding_constraint``, which accepts only
    ``Auto`` axes (``make_mesh`` defaults to ``Explicit`` ones)."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def data_axes(mesh) -> tuple:
    """The batch-sharding axes of a mesh ('pod' included when present)."""
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def axis_size(mesh, name: str) -> int:
    if name not in mesh.axis_names:
        return 1
    return mesh.shape[name]
