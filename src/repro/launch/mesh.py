"""Production mesh construction.

``make_production_mesh`` is a FUNCTION (not a module-level constant) so that
importing this module never touches jax device state; the dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax
import and only then builds the mesh.

``compat_make_mesh`` builds every mesh in this repo (and the tests) with
Auto axis types (``jax.make_mesh`` defaults to Explicit ones).
"""
from __future__ import annotations

from typing import Dict, Optional, Sequence, Tuple

import jax
import numpy as np


def compat_make_mesh(shape, axis_names, devices=None):
    """``jax.make_mesh`` with Auto axis types, over ``devices`` (the first
    ``prod(shape)`` of them; default all local devices) when given."""
    return jax.make_mesh(tuple(shape), tuple(axis_names),
                         (jax.sharding.AxisType.Auto,) * len(axis_names),
                         devices=devices)


def group_meshes(group_shapes: Dict, axis_names=("data", "model"),
                 devices: Optional[Sequence] = None) -> Dict:
    """Carve the host's devices into disjoint per-server meshes.

    ``group_shapes`` maps server id -> mesh shape tuple or None (solo
    twin).  Servers are assigned consecutive device slices in sorted-key
    order; ``None`` takes no devices (the solo twin computes on the
    default device).  Returns {server_id: Mesh | None} — feed it through
    ``DeviceGroup``/``GeoServingSystem(device_groups=...)``.  Raises when
    the shapes ask for more devices than the host exposes."""
    devs = list(devices if devices is not None else jax.devices())
    out, off = {}, 0
    for j in sorted(group_shapes):
        shape = group_shapes[j]
        if shape is None:
            out[j] = None
            continue
        n = int(np.prod(shape))
        if off + n > len(devs):
            raise ValueError(
                f"device groups need {off + n} devices, host has "
                f"{len(devs)} (shapes {group_shapes})")
        out[j] = compat_make_mesh(shape, axis_names, devs[off:off + n])
        off += n
    return out


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return compat_make_mesh(shape, axes)


def make_mesh_for(n_devices: Optional[int] = None, model_parallel: int = 1):
    """Small utility mesh for tests/examples (1..N local devices)."""
    n = n_devices or len(jax.devices())
    data = n // model_parallel
    return compat_make_mesh((data, model_parallel), ("data", "model"))


def mesh_axis_sizes(mesh) -> dict:
    return dict(zip(mesh.axis_names, mesh.devices.shape))


def batch_axes(mesh) -> Tuple[str, ...]:
    """Mesh axes that carry the batch (data-parallel) dimension."""
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)
