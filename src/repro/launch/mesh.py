"""Mesh construction.

FUNCTIONS (never module-level constants) so importing this module never
touches jax device state — the dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax
import, and smoke tests/benches must keep seeing 1 device.

Every mesh here has ``Auto`` axes: the model code places arrays with
``with_sharding_constraint`` through logical-axis rules
(``repro.parallel.api.shard``), which JAX accepts only on ``Auto`` axes
(``jax.make_mesh`` defaults to ``Explicit``).
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
from jax.sharding import AxisType

__all__ = ["make_mesh", "make_production_mesh"]


def make_mesh(shape: Sequence[int], axes: Sequence[str], *,
              devices: Optional[Sequence] = None):
    """A mesh of ``shape`` over ``axes`` with ``Auto`` axis types."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    """(16,16)=256 chips/pod ("data","model"); multi-pod adds a leading
    2-way "pod" axis (the slower DCN/ICI-optical dimension) = 512 chips."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)
