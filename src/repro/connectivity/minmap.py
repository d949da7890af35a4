"""Minimum-mapping operators (paper §II-B) as pure-JAX primitives.

Moved here from ``repro.core.labels`` (which remains as an alias) so the
``repro.connectivity`` package — the single public connectivity surface —
owns the math while ``repro.core`` holds only deprecation shims.

The paper's h-order minimum-mapping operator ``MM^h(L_u, L, w, v)``:

    z^h = min(L^h[w], L^h[v])           (L^h = h-fold composition of L)
    conditionally assign z^h into L_u at positions
    {w, v, L[w], L[v], ..., L^{h-1}[w], L^{h-1}[v]}

The paper implements the conditional assignment with an atomic CAS loop
(Eq. 4).  On TPU the equivalent race-free primitive is a *scatter-min*
(`L.at[idx].min(z)`): ``min`` is associative and commutative, so XLA's
scatter combiner reaches the identical fixed point deterministically
(DESIGN.md §3).
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro import tracing


def gather_chain(L: jax.Array, idx: jax.Array, order: int) -> Tuple[jax.Array, ...]:
    """Return (L^1[idx], ..., L^order[idx])."""
    out = []
    cur = L[idx]
    out.append(cur)
    for _ in range(order - 1):
        cur = L[cur]
        out.append(cur)
    return tuple(out)


def mm_update_stream(
    L: jax.Array, src: jax.Array, dst: jax.Array, order: int
) -> Tuple[jax.Array, jax.Array]:
    """Gather phase of ``MM^order``: the ``(targets, values)`` update stream.

    ``values`` is ``z = min(L^order[src], L^order[dst])`` per edge;
    ``targets`` are the conditional-assignment positions — the endpoints
    plus their 1..order-1 mapped vertices (Definition 3).  This is the
    single source of truth for the sweep's math: :func:`mm_relax` scatters
    the stream with XLA, the label-blocked Pallas kernel
    (`kernels.contour_mm.blocked`) scatters the identical stream through
    binned per-tile segment mins — which is what makes the two backends
    bit-exact per sweep.
    """
    if order < 1:
        raise ValueError(f"order must be >= 1, got {order}")
    chain_s = gather_chain(L, src, order)  # L[src], L^2[src], ...
    chain_d = gather_chain(L, dst, order)
    z = jnp.minimum(chain_s[-1], chain_d[-1])
    targets = [src, dst]
    for k in range(order - 1):
        targets.append(chain_s[k])
        targets.append(chain_d[k])
    return jnp.concatenate(targets), jnp.tile(z, len(targets))


def mm_relax(L: jax.Array, src: jax.Array, dst: jax.Array, order: int) -> jax.Array:
    """One parallel sweep of ``MM^order`` over every edge; returns new labels.

    This is the synchronous formulation: all reads see the input ``L`` and
    all conditional assignments combine by minimum, exactly Alg. 1 lines
    6-9 (``L_u`` initialised to ``L``, then ``L = L_u``).
    """
    idx, vals = mm_update_stream(L, src, dst, order)
    return L.at[idx].min(vals)


def pointer_jump(L: jax.Array, rounds: int = 1) -> jax.Array:
    """``L <- L[L]`` repeated; halves pointer-tree height per round.

    Used (a) as the in-iteration recompaction that adapts the paper's
    asynchronous updates to a functional runtime and (b) to realise the
    high-order ``C-m`` operator without length-m serial gather chains
    (DESIGN.md §3).
    """
    with jax.named_scope(tracing.POINTER_JUMP):
        for _ in range(rounds):
            L = jnp.minimum(L, L[L])
        return L


def converged_early(L: jax.Array, src: jax.Array, dst: jax.Array) -> jax.Array:
    """Paper §III-B2 early-convergence predicate.

    Converged iff for every edge (w, v):
        L[w] == L[v]  and  L[w] == L^2[w]  and  L[v] == L^2[v].
    """
    with jax.named_scope(tracing.CONVERGE_CHECK):
        lw, lv = L[src], L[dst]
        bad = (lw != lv) | (lw != L[lw]) | (lv != L[lv])
        return ~jnp.any(bad)


def is_star_forest(L: jax.Array) -> jax.Array:
    """True iff the pointer graph is a forest of stars (L[L] == L)."""
    return jnp.all(L[L] == L)


def check_labels_nonnegative(labels: jax.Array) -> None:
    """Eagerly reject negative labels (mirrors ``Graph.add_edges``).

    The ``min(init, iota)`` warm-start clamp lets negatives through, and
    XLA gather then silently clamps the out-of-range index to 0 — merging
    every poisoned vertex into component 0.  The check needs concrete
    values, so it is a no-op on tracers; eager callers (the ``solve``
    facade, ``solve_batch``, the distributed path) all funnel through it.
    """
    if not isinstance(labels, jax.core.Tracer) and labels.size:
        lo = int(labels.min())
        if lo < 0:
            raise ValueError(
                f"warm-start labels must be >= 0, got minimum {lo}; "
                "negative ids would be clamped to vertex 0 by XLA gather "
                "and silently merge the wrong components")


def resolve_init_labels(
    init: Optional[jax.Array], n_vertices: int, dtype
) -> jax.Array:
    """Initial label array for a (possibly warm-started) solve.

    ``None`` gives the identity labelling of Alg. 1 line 2.  A warm start
    passes the converged labels of a previous solve: any labelling with
    ``L[v]`` in the same component as ``v`` has the same fixed point, and
    min-mapping labels only ever decrease, so starting at the old fixed
    point is both correct and strictly ahead of the identity start.

    Two normalisations keep arbitrary caller input safe:

    * a shorter array (the graph grew vertices since the previous solve)
      is extended with identity labels for the new vertices;
    * the result is clamped to ``min(init, iota)`` so the identity
      invariant ``L[v] <= v`` (which every solver here preserves and the
      monotonicity guarantee is stated against) holds from iteration 0.

    Negative labels are rejected eagerly via
    :func:`check_labels_nonnegative` (see there for why); under a trace
    (e.g. ``solve`` called inside a user ``jax.jit``) the eager check
    cannot fire, so negatives are instead *neutralised* to the identity
    label — an always-valid cold start for that vertex — rather than left
    for XLA gather to clamp to vertex 0 and merge wrong components.
    """
    iota = jnp.arange(n_vertices, dtype=dtype)
    if init is None:
        return iota
    init = jnp.asarray(init).astype(dtype)
    check_labels_nonnegative(init)
    if init.shape[0] > n_vertices:
        raise ValueError(
            f"warm-start labels cover {init.shape[0]} vertices but the "
            f"graph has only {n_vertices}")
    if init.shape[0] < n_vertices:
        init = jnp.concatenate([init, iota[init.shape[0]:]])
    init = jnp.where(init < 0, iota, init)
    return jnp.minimum(init, iota)
