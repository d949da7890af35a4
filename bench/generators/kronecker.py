"""Graph500 Kronecker edge lists, drawn on the device from the seed.

The Graph500 generator: each of ``m = edge_factor * 2**scale`` edges
picks one quadrant of the adjacency matrix per bit of the vertex id,
with initiator probabilities A, B, C and D = 1 - A - B - C.  Duplicate
edges and self-loops are kept, as the generator emits them, and vertex
ids are permuted from the seed so that degree does not follow id.

Config keys: ``scale``, ``edge_factor`` and ``initiator`` ([A, B, C]).
``bench/batches/kronecker.py`` draws fresh edges of the same graph.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench import seeding

# streams of one seed
EDGES, PERM, BATCHES = 0, 1, 2


def kronecker(key, perm, m: int, scale: int, initiator: tuple):
    """``m`` Kronecker edges from ``key``, ids mapped through ``perm``."""
    a, b, c = initiator
    ab = a + b
    a_norm, c_norm = a / ab, c / (1.0 - ab)

    def one_bit(bit, edges):
        src, dst = edges
        k_row, k_col = jax.random.split(jax.random.fold_in(key, bit))
        # row bit 1 with probability C + D; column bit 1 with B / (A + B)
        # in the top half and D / (C + D) in the bottom half
        row = jax.random.uniform(k_row, (m,)) > ab
        col = jax.random.uniform(k_col, (m,)) > jnp.where(row, c_norm, a_norm)
        return (src | (row.astype(jnp.int32) << bit),
                dst | (col.astype(jnp.int32) << bit))

    zeros = jnp.zeros((m,), jnp.int32)
    src, dst = jax.lax.fori_loop(0, scale, one_bit, (zeros, zeros))
    return perm[src], perm[dst]


_edges = jax.jit(kronecker, static_argnames=("m", "scale", "initiator"))


def perm(cfg: dict, seed: int):
    """The seed's vertex-id permutation, on the device."""
    # a host permutation of 2**20 ids takes milliseconds; on the device
    # it is one more program to compile in every checkout
    n = 1 << int(cfg["scale"])
    return jnp.asarray(seeding.rng(seed, PERM).permutation(n), jnp.int32)


def params(cfg: dict) -> dict:
    return dict(scale=int(cfg["scale"]),
                initiator=tuple(float(p) for p in cfg["initiator"]))


def draw(cfg: dict, seed: int):
    """``(src, dst, n)``: the whole edge list on the device."""
    n = 1 << int(cfg["scale"])
    m = int(cfg["edge_factor"]) * n
    src, dst = _edges(seeding.key(seed, EDGES), perm(cfg, seed), m=m,
                      **params(cfg))
    return src, dst, n
