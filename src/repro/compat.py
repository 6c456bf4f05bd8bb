"""Mesh helpers shared by every mesh construction and installation.

Thin wrappers over the installed JAX's mesh APIs (``jax.make_mesh`` with
Auto axis types, ``jax.set_mesh``, ``jax.shard_map``,
``jax.lax.axis_size``) so call sites stay short and uniform.
"""

from __future__ import annotations

from typing import Sequence

import jax

__all__ = ["make_mesh", "set_mesh", "make_abstract_mesh", "shard_map",
           "axis_size"]

shard_map = jax.shard_map


def axis_size(axis_name: str) -> int:
    """Static size of a named mesh axis inside a shard_map island."""
    return jax.lax.axis_size(axis_name)


def make_mesh(axis_shapes: Sequence[int], axis_names: Sequence[str],
              *, devices=None):
    """``jax.make_mesh`` with Auto axis types."""
    names = tuple(axis_names)
    kwargs = {} if devices is None else {"devices": devices}
    return jax.make_mesh(tuple(axis_shapes), names,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(names),
                         **kwargs)


def set_mesh(mesh):
    """Context manager installing ``mesh`` as the ambient mesh."""
    return jax.set_mesh(mesh)


def make_abstract_mesh(axis_shapes: Sequence[int],
                       axis_names: Sequence[str]):
    """Device-free mesh for sharding-rule metadata (no allocation)."""
    return jax.sharding.AbstractMesh(tuple(axis_shapes), tuple(axis_names))
