"""Trace-time mesh context: launchers register the mesh so deep model code
(the shard_map MoE path) can build collectives without threading the mesh
through every call signature."""
from __future__ import annotations

from typing import Optional

from jax.sharding import Mesh

_CURRENT: Optional[Mesh] = None


def set_current_mesh(mesh: Optional[Mesh]) -> None:
    global _CURRENT
    _CURRENT = mesh


def current_mesh() -> Optional[Mesh]:
    return _CURRENT

