"""Ambient mesh for layers that need manual collectives (shard_map MoE).

``make_workload`` / the train driver set this before tracing; layers read it.
``None`` means single-host execution (CPU tests) → layers fall back to their
pure-pjit implementations.
"""
from __future__ import annotations

from typing import Optional, Tuple

from jax.sharding import Mesh

_MESH: Optional[Mesh] = None


def set_mesh(mesh: Optional[Mesh]) -> None:
    global _MESH
    _MESH = mesh


def get_mesh() -> Optional[Mesh]:
    return _MESH


def batch_axes() -> Tuple[str, ...]:
    if _MESH is None:
        return ()
    return ("pod", "data") if "pod" in _MESH.axis_names else ("data",)
