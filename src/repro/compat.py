"""The three mesh/partitioning spellings the tree uses, pinned to the
installed JAX (0.9): ``shard_map``, ``make_mesh`` with every axis
``Auto`` (GSPMD may still partition over it), and ``set_mesh``."""
from __future__ import annotations

import jax
from jax import shard_map, set_mesh

__all__ = ["shard_map", "make_mesh", "set_mesh"]


def make_mesh(axis_shapes, axis_names):
    """``jax.make_mesh`` with Auto axis types (the sharding rules in
    ``parallel/sharding.py`` place arrays explicitly and leave the rest
    to GSPMD)."""
    return jax.make_mesh(axis_shapes, axis_names,
                         axis_types=(jax.sharding.AxisType.Auto,)
                         * len(axis_names))
