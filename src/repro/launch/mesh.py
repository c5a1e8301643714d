"""Mesh construction. A FUNCTION, not a module-level constant, so importing
this module never touches jax device state."""
from __future__ import annotations

import jax
import numpy as np
from jax.sharding import AxisType, Mesh


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: 16x16 = 256 chips (data, model). Multi-pod adds a leading
    "pod" axis: 2 x 16 x 16 = 512 chips. The dry-run launcher sets
    XLA_FLAGS=--xla_force_host_platform_device_count=512 before any jax
    import so these meshes exist on CPU."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_dev_mesh(devices=None) -> Mesh:
    """(data, model) = (n, 1) mesh with production axis names over
    ``devices`` (default: every device of the process): every device given
    is used along "data", never a silently smaller mesh."""
    devices = list(jax.devices() if devices is None else devices)
    if not devices:
        raise ValueError("make_dev_mesh needs at least one device")
    grid = np.asarray(devices, dtype=object).reshape(-1, 1)
    return Mesh(grid, ("data", "model"), axis_types=(AxisType.Auto,) * 2)
