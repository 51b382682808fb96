"""Mesh constructors with every axis ``AxisType.Auto``.

The solver's sharded paths let the compiler propagate shardings inside
``shard_map`` bodies, which is what Auto axes mean; these two helpers
spell that out once instead of at every call site.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(axis_shapes, axis_names):
    """``jax.make_mesh`` over the available devices, all axes Auto."""
    return jax.make_mesh(axis_shapes, axis_names,
                         axis_types=(AxisType.Auto,) * len(axis_names))


def abstract_mesh(axis_shapes, axis_names):
    """``jax.sharding.AbstractMesh`` of the same shape, all axes Auto."""
    return jax.sharding.AbstractMesh(
        axis_shapes, axis_names,
        axis_types=(AxisType.Auto,) * len(axis_names))
