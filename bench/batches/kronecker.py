"""Fresh edges of a Graph500 Kronecker graph, in batches, for a stream.

The same initiator and vertex permutation as the configuration's graph
(``bench/generators/kronecker.py``), with keys of their own: batch ``i``
of chunk ``j`` is drawn from the seed, ``j`` and ``i`` alone, so a chunk
drawn again is the same edges.  A whole chunk is one device call.
"""
from __future__ import annotations

import functools

import jax

from bench import seeding
from bench.generators import kronecker as gen


@functools.partial(jax.jit, static_argnames=("m", "scale", "initiator"))
def _chunk(keys, perm, m, scale, initiator):
    return jax.vmap(lambda k: gen.kronecker(k, perm, m, scale, initiator))(
        keys)


class Batches:
    """Chunks of ``count`` batches of ``k`` edges each."""

    def __init__(self, cfg: dict, seed: int, k: int, count: int):
        self.k, self.count = int(k), int(count)
        self.key = seeding.key(seed, gen.BATCHES)
        self.perm = gen.perm(cfg, seed)
        self.params = gen.params(cfg)

    def chunk(self, index: int) -> list:
        """Chunk ``index``: a list of ``count`` ``(src, dst)`` pairs on the
        device, ready."""
        keys = jax.random.split(jax.random.fold_in(self.key, index),
                                self.count)
        src, dst = _chunk(keys, self.perm, m=self.k, **self.params)
        return jax.block_until_ready(list(zip(src, dst)))
