"""Production meshes.

Functions (never module-level constants) so importing this module touches no
jax device state — the dry-run must set XLA_FLAGS before first jax init.
"""
from __future__ import annotations

import jax


def make_mesh(shape, axes) -> jax.sharding.Mesh:
    """``jax.make_mesh`` with every axis ``Auto`` (GSPMD-propagated)."""
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    """Single pod: (16, 16) = 256 chips (data, model).
    Multi-pod: (2, 16, 16) = 512 chips (pod, data, model)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(shape=(2, 2), axes=("data", "model")) -> jax.sharding.Mesh:
    """Small mesh over forced host devices — tests and local dry-runs."""
    return make_mesh(shape, axes)


def dp_axes_for(mesh: jax.sharding.Mesh) -> tuple[str, ...]:
    return tuple(a for a in mesh.axis_names if a != "model")
