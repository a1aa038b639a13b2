"""Production mesh construction (multi-pod dry-run contract).

Defined as functions (never module-level constants) so importing this module
never touches jax device state.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def auto_mesh(shape: tuple[int, ...], axes: tuple[str, ...]):
    """``jax.make_mesh`` with ``Auto`` axes — the one mesh spelling here.

    JAX 0.9 makes ``Explicit`` axes by default; the SOAR reduce, the
    training step and the MoE EP path are written for ``Auto`` axes
    (``with mesh:``, ``with_sharding_constraint``, ``shard_map``).
    """
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return auto_mesh(shape, axes)


def make_test_mesh(n_data: int = 2, n_model: int = 2):
    """Small mesh for unit tests (requires forced host device count)."""
    return auto_mesh((n_data, n_model), ("data", "model"))


def mesh_axis_sizes(mesh) -> dict[str, int]:
    return dict(zip(mesh.axis_names, mesh.devices.shape))


def dp_axes(mesh) -> tuple[str, ...]:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def dp_size(mesh) -> int:
    sizes = mesh_axis_sizes(mesh)
    out = 1
    for a in dp_axes(mesh):
        out *= sizes[a]
    return out
