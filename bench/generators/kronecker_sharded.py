"""Graph500 Kronecker edge lists drawn on the mesh of the cell's chips.

The edges of ``bench/generators/kronecker.py`` for the same configuration
and seed, bit for bit, but drawn by the chips of a ``("data",)`` mesh
over the first ``shards`` devices (``bench.drive.mesh_of``): the same
``kronecker()``, jitted with its outputs split along the mesh's edge
axis, so each chip computes and holds only its contiguous block of the
edges, as ``solve(graph, mesh=...)`` shards them.  JAX's partitionable
threefry (``jax_threefry_partitionable``, on by default since JAX 0.5)
gives every element the same random bits however the array is split.

Config keys: those of ``kronecker``, and ``shards``.
"""
from __future__ import annotations

import types

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from bench import drive, seeding
from bench.generators import kronecker


def draw(cfg: dict, seed: int):
    """``(src, dst, n)``: the whole edge list, split over the mesh."""
    if not jax.config.jax_threefry_partitionable:
        raise RuntimeError("drawing on a mesh needs jax_threefry_partitionable"
                           " on, or the edges depend on the split")
    shards = int(cfg["shards"])
    mesh = drive.mesh_of(types.SimpleNamespace(name=cfg["name"],
                                               chips=shards))
    n = 1 << int(cfg["scale"])
    m = int(cfg["edge_factor"]) * n
    if m % shards:
        raise ValueError(f"{m} edges do not split evenly over {shards} "
                         "chips")
    edges = jax.jit(kronecker.kronecker,
                    static_argnames=("m", "scale", "initiator"),
                    out_shardings=NamedSharding(mesh, P(drive.EDGE_AXIS)))
    src, dst = edges(seeding.key(seed, kronecker.EDGES),
                     kronecker.perm(cfg, seed), m=m, **kronecker.params(cfg))
    return src, dst, n
