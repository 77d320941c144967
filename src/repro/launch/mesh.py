"""Production mesh definitions.

``make_production_mesh`` is a FUNCTION (never a module-level constant) so
importing this module touches no jax device state — required because the
dry-run must set XLA_FLAGS before any jax initialization.

Topology: TPU v5e pods of 16x16 = 256 chips; the multi-pod mesh stacks a
``pod`` axis (2 pods = 512 chips) used purely for data parallelism (DCN
between pods is slower than ICI; only gradient/batch collectives cross it).
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
from jax.sharding import AxisType, Mesh


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh(model: int = 1, data: Optional[int] = None) -> Mesh:
    """Small mesh over the locally visible devices (tests / examples)."""
    n = len(jax.devices())
    data = data or max(n // model, 1)
    return jax.make_mesh((data, model), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)


def mesh_chip_count(mesh: Mesh) -> int:
    n = 1
    for s in mesh.devices.shape:
        n *= s
    return n
