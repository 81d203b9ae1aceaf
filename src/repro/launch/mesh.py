"""Production mesh construction.

``make_production_mesh`` is a FUNCTION (not a module-level constant) so that
importing this module never touches jax device state.  Single pod: a
(16, 16) = 256-chip (data, model) grid; multi-pod: (2, 16, 16) = 512 chips
with a leading "pod" axis that composes with "data" for batch/FSDP sharding
(cross-pod traffic is the cheap DP all-reduce; TP collectives stay
intra-pod).
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType, Mesh


def _mesh(shape, axes) -> Mesh:
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_host_mesh(data: int = 2, model: int = 2) -> Mesh:
    """Small mesh for CPU multi-device tests (run under
    XLA_FLAGS=--xla_force_host_platform_device_count=N)."""
    return _mesh((data, model), ("data", "model"))
