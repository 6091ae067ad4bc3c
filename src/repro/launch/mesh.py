"""Production meshes.

Defined as FUNCTIONS so importing this module never touches jax device
state (the dry-run must set XLA_FLAGS before any device query).

Topology: TPU v5e-style pods of 256 chips arranged (16, 16) =
(data, model); the multi-pod mesh stacks 2 pods on a leading "pod" axis
(data-parallel across DCN).  Smaller debug meshes for CPU tests come from
``make_debug_mesh``.
"""

from __future__ import annotations

import jax
from jax.sharding import Mesh


def _auto_mesh(shape, axes) -> Mesh:
    """A mesh whose axes all shard by GSPMD propagation (``Auto``)."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_debug_mesh(shape=(2, 2), axes=("data", "model")) -> Mesh:
    return _auto_mesh(shape, axes)
