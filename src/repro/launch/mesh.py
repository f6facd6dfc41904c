"""Production mesh construction.

Defined as functions (never module-level constants) so importing this module
never touches jax device state. The dry-run process sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax
import; everything else sees the real device count.
"""
from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
from jax.sharding import AxisType, Mesh


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    """16x16 (one v5e pod's worth of chips for this study) or 2x16x16."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...]) -> Mesh:
    """Auto-typed mesh: the model code places arrays with
    ``with_sharding_constraint`` and leaves the rest to the compiler, which
    jax's default Explicit axes refuse (e.g. the embedding gather)."""
    n = math.prod(shape)
    devices = jax.devices()
    if len(devices) < n:
        raise RuntimeError(
            f"mesh {shape} needs {n} devices, have {len(devices)} "
            "(dry-run must set --xla_force_host_platform_device_count)")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices[:n])


def make_host_mesh(model_parallel: int = 1) -> Mesh:
    """Small mesh over whatever devices exist (CPU tests / examples)."""
    n = len(jax.devices())
    mp = min(model_parallel, n)
    return make_mesh((n // mp, mp), ("data", "model"))


def submesh(mesh: Mesh, axis: str, lo: int, hi: int) -> Mesh:
    """Carve a contiguous partition along one mesh axis (the Flux-partition
    analogue for real-mode co-scheduling; see core/partition.py)."""
    idx = mesh.axis_names.index(axis)
    devs = mesh.devices
    slicer = [slice(None)] * devs.ndim
    slicer[idx] = slice(lo, hi)
    sub = devs[tuple(slicer)]
    return Mesh(sub, mesh.axis_names, axis_types=mesh.axis_types)
