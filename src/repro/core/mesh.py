"""Worker meshes for the SPMD engine paths.

``jax.make_mesh`` builds ``Explicit`` axes by default, under which every jitted
program outside ``shard_map`` (the out-of-core tails in store/residency.py,
which take mesh-sharded operands) must carry sharding-typed reshapes.  The
engine's programs are written for GSPMD's automatic propagation, so every mesh
the engine and the server use goes through :func:`as_auto_mesh`, and callers
build theirs with :func:`worker_mesh`.
"""
from __future__ import annotations

import jax
import numpy as np
from jax.sharding import AxisType, Mesh

__all__ = ["as_auto_mesh", "worker_mesh"]


def as_auto_mesh(mesh: Mesh | None) -> Mesh | None:
    """The same devices and axis names with every axis ``AxisType.Auto``."""
    if mesh is None:
        return None
    if all(t == AxisType.Auto for t in mesh.axis_types):
        return mesh
    return Mesh(mesh.devices, mesh.axis_names,
                axis_types=(AxisType.Auto,) * len(mesh.axis_names))


def worker_mesh(count: int | None = None, axis_name: str = "workers", *,
                devices=None) -> Mesh:
    """1-D ``Auto`` mesh of ``count`` workers over ``devices`` (default: the
    first ``count`` of ``jax.devices()``, all of them when count is None)."""
    devices = list(jax.devices() if devices is None else devices)
    count = len(devices) if count is None else int(count)
    if count > len(devices):
        raise ValueError(f"worker_mesh needs {count} devices, have {len(devices)}")
    return Mesh(np.asarray(devices[:count]), (axis_name,),
                axis_types=(AxisType.Auto,))
