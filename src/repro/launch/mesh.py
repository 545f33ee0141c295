"""Production mesh construction.

Kept as FUNCTIONS (never module-level constants) so importing this module
never touches jax device state — required for the dry-run's
``xla_force_host_platform_device_count`` trick to work, and for smoke
tests/benches to keep seeing exactly 1 CPU device.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import numpy as np
from jax.sharding import Mesh


def mesh_context(mesh):
    """Ambient-mesh context manager (``jax.set_mesh``): makes the mesh
    visible to ``with_sharding_constraint`` / shard_hint inside jitted
    bodies."""
    return jax.set_mesh(mesh)


def make_production_mesh(*, multi_pod: bool = False):
    """TPU v5e production mesh: one pod = 16x16 = 256 chips, axes
    ("data", "model"); the multi-pod mesh adds a leading "pod" axis over
    2 pods = 512 chips (DCN between pods, ICI within)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def dp_axes(mesh) -> Tuple[str, ...]:
    """The data-parallel axes of a production mesh (batch sharding)."""
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def tp_axis(mesh) -> str:
    return "model"


def make_elastic_mesh(
    n_failed_hosts: int = 0, *, multi_pod: bool = False,
    base_mesh: Optional[Mesh] = None,
):
    """Degraded mesh after losing ``n_failed_hosts`` hosts: shrink the
    data axis (model axis untouched so param sharding is stable) —
    checkpoint/manager.py reshards state onto this mesh on restart, and
    ``ServeEngine.remesh`` replays in-flight slots onto it.

    With ``base_mesh`` the degraded mesh reuses the SURVIVING devices of
    that mesh (each data-axis row is one host): the trailing
    ``n_failed_hosts`` rows drop, the model axis keeps its exact device
    order. Without it, the production 16x16 (or 32x16 multi-pod) shape
    is rebuilt from the default device list."""
    if base_mesh is not None:
        names = base_mesh.axis_names
        if "data" not in names:
            raise ValueError(f"base_mesh has no 'data' axis: {names}")
        devs = np.asarray(base_mesh.devices)
        rows = devs.shape[names.index("data")] - n_failed_hosts
        if rows < 1:
            raise ValueError("no capacity left")
        idx = [slice(None)] * devs.ndim
        idx[names.index("data")] = slice(0, rows)
        return Mesh(devs[tuple(idx)], names)
    rows = (32 if multi_pod else 16) - n_failed_hosts
    if rows < 1:
        raise ValueError("no capacity left")
    return jax.make_mesh((rows, 16), ("data", "model"))


def make_host_mesh(shape: Tuple[int, ...] = (1, 8), axes=("data", "model")):
    """Small explicit mesh over the first ``prod(shape)`` local devices —
    the forced-CPU-device test/bench entry point (set
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` before
    importing jax)."""
    n = int(np.prod(shape))
    devs = np.asarray(jax.devices()[:n]).reshape(shape)
    return Mesh(devs, axes)
