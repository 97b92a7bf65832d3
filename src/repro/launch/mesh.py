"""Production mesh factories.

Functions (not module-level constants) so importing never touches jax
device state. Production target: TPU v5e, 256 chips/pod, 16x16 (data, model);
multi-pod = 2 pods x 256 = 512 chips with a leading "pod" axis that composes
with data parallelism (docs/DESIGN.md §6).
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax


def make_mesh(shape, axes):
    """``jax.make_mesh`` with every axis explicitly ``Auto``."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    if multi_pod:
        return make_mesh((2, 16, 16), ("pod", "data", "model"))
    return make_mesh((16, 16), ("data", "model"))


def make_debug_mesh(data: int = 2, model: int = 2, pod: int = 0):
    """Small mesh for subprocess tests on N virtual CPU devices."""
    if pod:
        return make_mesh((pod, data, model), ("pod", "data", "model"))
    return make_mesh((data, model), ("data", "model"))


def batch_axes(mesh) -> Tuple[str, ...]:
    return ("pod", "data") if "pod" in mesh.shape else ("data",)


def n_chips(mesh) -> int:
    return mesh.devices.size
