"""Mesh construction — the one place meshes are built.

Single pod: 16x16 = 256 chips (v5e pod), axes (data, model).
Multi-pod:  2x16x16 = 512 chips, axes (pod, data, model) — `pod` is the
outermost (DCN-connected) axis and carries pure data parallelism plus the
query-wave axis of the TCQ engine.

Every axis is ``AxisType.Auto``: ``jax.make_mesh`` defaults to Explicit
axes, under which the sharded step's lane refills (``buf.at[idx].set``)
and gathers raise ``ShardingTypeError``; the TCQ engine places its arrays
with ``NamedSharding`` and lets the partitioner propagate the rest.

FUNCTIONS, not module constants: importing this module never touches jax
device state (the dry-run sets XLA_FLAGS before any jax initialization).
"""

from __future__ import annotations

import jax
import numpy as np
from jax.sharding import AxisType, Mesh


def make_mesh(shape, axes, *, devices=None) -> Mesh:
    """Auto-axis mesh of ``shape`` over ``devices`` (default: the local
    devices, in ``jax.make_mesh``'s topology-aware order)."""
    shape, axes = tuple(shape), tuple(axes)
    auto = (AxisType.Auto,) * len(axes)
    if devices is None:
        return jax.make_mesh(shape, axes, axis_types=auto)
    return Mesh(np.asarray(devices).reshape(shape), axes, axis_types=auto)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(model: int = 1):
    """Mesh over the locally available devices (tests/examples)."""
    n = len(jax.devices())
    return make_mesh((n // model, model), ("data", "model"))


def dp_axes(mesh) -> tuple:
    """Mesh axes carrying the batch dimension."""
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)
