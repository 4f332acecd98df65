"""The one import point for the version-sensitive `jax.sharding` surface.

FIG001 (`analysis/rules/compat_pin.py`) keeps the raw spellings of these
symbols inside this module, so a future JAX rename touches one file. The
module is written against the installed JAX (0.9) and carries no branch for
any other version:

  AxisType             `jax.sharding.AxisType`
  make_mesh            `jax.make_mesh`
  make_abstract_mesh   `jax.sharding.AbstractMesh(shapes, names, axis_types=)`
  shard_map            `jax.shard_map`
  axis_size            `jax.lax.axis_size`
"""

from __future__ import annotations

from typing import Sequence

import jax
from jax import shard_map
from jax.lax import axis_size
from jax.sharding import AbstractMesh, AxisType

__all__ = ["AxisType", "make_mesh", "make_abstract_mesh", "shard_map",
           "axis_size"]


def make_mesh(axis_shapes: Sequence[int], axis_names: Sequence[str], *,
              axis_types=None, devices=None):
    """`jax.make_mesh` with keyword-only ``axis_types`` / ``devices``."""
    return jax.make_mesh(axis_shapes, axis_names, axis_types=axis_types,
                         devices=devices)


def make_abstract_mesh(axis_shapes: Sequence[int], axis_names: Sequence[str],
                       *, axis_types=None):
    """A device-free `AbstractMesh` (for sharding-rule tests)."""
    return AbstractMesh(tuple(axis_shapes), tuple(axis_names),
                        axis_types=axis_types)
