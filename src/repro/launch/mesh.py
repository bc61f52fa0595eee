"""Production meshes (v5e): single-pod 16x16 = 256 chips, multi-pod 2x16x16.

Functions, not module constants — importing this module never touches jax
device state (the dry-run sets XLA_FLAGS before any jax import).
"""
from __future__ import annotations

from typing import Tuple

import jax


def make_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...],
              devices=None) -> jax.sharding.Mesh:
    """jax.make_mesh with Auto axes: the model code places activations
    through sharding constraints and GSPMD propagation, which Explicit axes
    (the make_mesh default) turn into type errors."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def data_axes(mesh: jax.sharding.Mesh) -> Tuple[str, ...]:
    """Axes that carry data parallelism (pods do DP over DCI)."""
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)


def axis_size(mesh: jax.sharding.Mesh, name: str) -> int:
    return dict(zip(mesh.axis_names, mesh.devices.shape)).get(name, 1)
