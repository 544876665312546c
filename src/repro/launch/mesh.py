"""Production mesh construction (TPU v5e target).

``make_production_mesh`` is a FUNCTION (not a module constant) so importing
this module never touches jax device state; callers (dryrun.py) must set
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` BEFORE the first
jax import to fabricate the placeholder devices.
"""

from __future__ import annotations

from typing import Tuple

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: 16x16 = 256 chips (data, model).  Multi-pod: 2 pods of
    256 = 512 chips with a leading "pod" axis (data-parallel across the
    inter-pod DCN/ICI boundary)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def dp_axes_of(mesh) -> Tuple[str, ...]:
    """Names of the data-parallel axes (pod included when present)."""
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def make_smoke_mesh():
    """1-device mesh for CPU smoke runs of the sharded code path."""
    return jax.make_mesh((1, 1), ("data", "model"), axis_types=(AxisType.Auto,) * 2)
