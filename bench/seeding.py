"""Keys and host generators derived from a run's ``--seed``.

``--seed`` may be any non-negative whole number, wider than 32 bits;
``numpy.random.SeedSequence`` takes it whole, so no two seeds share a
stream.  ``stream`` separates the draws of one run (edges, vertex
permutation, stream batches).
"""
from __future__ import annotations

import numpy as np


def words(seed: int, stream: int) -> np.ndarray:
    """Two uint32 words of entropy for ``(seed, stream)``."""
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    return np.random.SeedSequence([seed, stream]).generate_state(2)


def key(seed: int, stream: int):
    """A JAX PRNG key for ``(seed, stream)``."""
    import jax

    return jax.random.wrap_key_data(words(seed, stream), impl="threefry2x32")


def rng(seed: int, stream: int) -> np.random.Generator:
    """A NumPy generator for ``(seed, stream)``."""
    return np.random.default_rng(words(seed, stream))
