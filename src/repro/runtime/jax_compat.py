"""Mesh and shard_map construction in one place.

Every mesh and shard_map in the repo is built here, with the call
shapes the rest of the code uses: meshes whose axes are all explicit
``Auto`` axes, and ``shard_map`` with the varying-axis checker as a
keyword.
"""
from __future__ import annotations

from typing import Sequence

import jax
from jax.sharding import AxisType, Mesh

__all__ = ["make_mesh", "shard_map"]


def make_mesh(axis_shapes: Sequence[int], axis_names: Sequence[str], *,
              devices=None) -> Mesh:
    """``jax.make_mesh`` with every axis an ``Auto`` axis (a plain
    manual-collective axis under shard_map)."""
    return jax.make_mesh(tuple(axis_shapes), tuple(axis_names),
                         axis_types=(AxisType.Auto,) * len(axis_names),
                         devices=devices)


def shard_map(f, *, mesh, in_specs, out_specs, check_vma: bool = True):
    """``jax.shard_map``; ``check_vma`` toggles the varying-axis
    checker."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_vma)
