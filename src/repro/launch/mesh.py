"""Production meshes (TPU v5e).

Functions, not module-level constants: importing this module never touches
jax device state (device count is locked at first jax init, and only the
dry-run entrypoint sets the 512-device host-platform flag).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType, Mesh


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    """Single pod: (16, 16) = 256 chips ('data', 'model').
    Multi-pod: (2, 16, 16) = 512 chips ('pod', 'data', 'model')."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh(data: int = 1, model: int = 1) -> Mesh:
    """Small mesh over whatever local devices exist (tests, examples)."""
    return jax.make_mesh(
        (data, model), ("data", "model"), axis_types=(AxisType.Auto,) * 2
    )
