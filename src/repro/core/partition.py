"""Partitioning bridge: the paper's Flux partitions realized both as node
ranges (simulation) and as jax device submeshes (real mode) — a tightly
coupled task is co-scheduled onto one partition's submesh via pjit."""
from __future__ import annotations

from dataclasses import dataclass
from typing import List, Optional

import numpy as np


@dataclass
class MeshPartition:
    index: int
    mesh: "jax.sharding.Mesh"          # noqa: F821


def carve_submeshes(mesh, n_partitions: int, axis: str = "data"
                    ) -> List[MeshPartition]:
    """Split a Mesh into disjoint contiguous submeshes along ``axis``.
    Each partition keeps the full extent of every other axis (so tensor
    parallelism inside a partition is untouched)."""
    from jax.sharding import Mesh
    idx = mesh.axis_names.index(axis)
    size = mesh.devices.shape[idx]
    n_partitions = min(n_partitions, size)
    step = size // n_partitions
    parts = []
    for i in range(n_partitions):
        lo = i * step
        hi = (i + 1) * step if i < n_partitions - 1 else size
        slicer = [slice(None)] * mesh.devices.ndim
        slicer[idx] = slice(lo, hi)
        parts.append(MeshPartition(i, Mesh(mesh.devices[tuple(slicer)],
                                           mesh.axis_names,
                                           axis_types=mesh.axis_types)))
    return parts
