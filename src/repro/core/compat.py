"""Thin helpers over the JAX APIs that build meshes and shard_maps.

Everything that builds a mesh or a shard_map goes through these helpers, so
the repo's defaults (Auto axis types, ``check_vma`` off) live in one place.
"""

from __future__ import annotations

from typing import Sequence

import jax
from jax.sharding import AxisType


def shard_map(f, *, mesh, in_specs, out_specs, check_vma: bool = False):
    """``jax.shard_map`` with varying-axis checks off by default: the
    accumulator's collectives produce deliberately replicated outputs."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                         check_vma=check_vma)


def axis_size(axis) -> int:
    """Static size of a named mesh axis (or tuple of axes) inside shard_map."""
    axes = axis if isinstance(axis, (tuple, list)) else (axis,)
    n = 1
    for a in axes:
        n *= jax.lax.axis_size(a)
    return n


def cost_analysis(compiled) -> dict:
    """``compiled.cost_analysis()`` as a plain dict (empty when the backend
    reports nothing)."""
    return dict(compiled.cost_analysis() or {})


def make_mesh(shape: Sequence[int], names: Sequence[str], devices=None):
    """``jax.make_mesh`` with Auto axis types."""
    shape, names = tuple(shape), tuple(names)
    return jax.make_mesh(shape, names, devices=devices,
                         axis_types=(AxisType.Auto,) * len(shape))
