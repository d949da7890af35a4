"""Delaunay triangulations of random points: the DIMACS10 ``delaunay_n``
family.

DIMACS10's ``delaunay_n<s>`` is the Delaunay triangulation of ``2**s``
random points in the unit square.  The points are drawn uniformly on the
host and triangulated by scipy's Qhull; each triangle's sides become
edges, each undirected edge once.  Vertex ids are the points' draw
order, so an id says nothing of where its point lies.

The points are one fixed draw, from the configuration's ``points_seed``,
as the published graph is one fixed file: each draw is another graph,
whose solve takes one sweep more or less by where vertex 0 falls, so a
graph per run seed would make the seed change the work.  A run's seed
shuffles the order of the edge list instead: the same graph and the same
work, in another order.

Config keys: ``scale`` and ``points_seed``.
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from bench import seeding

ORDER = 3


def triangulation_edges(points: np.ndarray) -> tuple:
    """``(src, dst)`` int32: the sides of the Delaunay triangles of
    ``points`` (shape ``(n, 2)``), each once, ``src < dst``, sorted."""
    from scipy.spatial import Delaunay

    n = len(points)
    tri = Delaunay(points)
    if len(tri.coplanar):
        raise ValueError(f"{len(tri.coplanar)} points left out of the "
                         "triangulation")
    s = tri.simplices.astype(np.int64)
    sides = np.concatenate([s[:, [0, 1]], s[:, [1, 2]], s[:, [0, 2]]])
    sides.sort(axis=1)
    key = np.unique(sides[:, 0] * n + sides[:, 1])
    return (key // n).astype(np.int32), (key % n).astype(np.int32)


def draw(cfg: dict, seed: int):
    """``(src, dst, n)``: the whole edge list on the device."""
    n = 1 << int(cfg["scale"])
    points = np.random.default_rng(int(cfg["points_seed"])).random((n, 2))
    src, dst = triangulation_edges(points)
    order = seeding.rng(seed, ORDER).permutation(len(src))
    return jnp.asarray(src[order]), jnp.asarray(dst[order]), n
