"""The small jax mesh/sharding API surface this repo leans on, in one place.

The codebase targets the current API (``jax.shard_map``,
``jax.sharding.AxisType``, ``AbstractMesh(sizes, names)``, dict-valued
``compiled.cost_analysis()``).  These helpers give every call site the
same Auto-typed meshes and one import for ``shard_map``.
"""
from __future__ import annotations

from typing import Sequence

import jax

shard_map = jax.shard_map


def mesh_axis_kwargs(n_axes: int) -> dict:
    """``axis_types=(Auto, ...)`` for a mesh with ``n_axes`` axes."""
    return {"axis_types": (jax.sharding.AxisType.Auto,) * n_axes}


def make_mesh(axis_shapes: Sequence[int],
              axis_names: Sequence[str]) -> jax.sharding.Mesh:
    """`jax.make_mesh` with Auto axis types."""
    return jax.make_mesh(tuple(axis_shapes), tuple(axis_names),
                         **mesh_axis_kwargs(len(axis_names)))


def device_mesh(devices, axis_names: Sequence[str]) -> jax.sharding.Mesh:
    """`jax.sharding.Mesh` over an explicit device array, Auto-typed."""
    return jax.sharding.Mesh(devices, tuple(axis_names),
                             **mesh_axis_kwargs(len(axis_names)))


def abstract_mesh(axis_sizes: Sequence[int], axis_names: Sequence[str]):
    """`AbstractMesh` over the given axis sizes and names."""
    from jax.sharding import AbstractMesh
    return AbstractMesh(tuple(axis_sizes), tuple(axis_names))


def cost_analysis(compiled) -> dict:
    """``compiled.cost_analysis()`` as a plain dict."""
    return dict(compiled.cost_analysis())
