"""Work-adaptive edge-frontier contraction for the min-mapping fixpoint.

The paper's per-iteration cost is O(m): every sweep touches every edge
(Alg. 1 line 5).  Its own optimizations — early convergence (§III-B2) and
asynchronous updates (§III-B1) — exist precisely because most edges become
intra-component after a few iterations and then contribute nothing.  This
module makes that observation structural, adapting ConnectIt's sampling
phase and Afforest's skip-the-largest-component trick (PAPERS.md) to a
jit-compiled functional runtime:

1. **Sampling phase** — the first ``sampling`` iterations sweep only a
   *sample* of the edge list.  Which sample is a pluggable
   :class:`SamplingStrategy` (ConnectIt's central axis): the default
   ``"prefix"`` strategy sweeps a deterministic prefix
   (``m // SAMPLE_PREFIX_DENOM`` edges); ``"kout"`` is the
   Afforest/k-out neighbour-subgraph sampler (each vertex's first ``k``
   incident edges); ``"bfs"`` grows low-diameter balls around
   high-degree seed vertices.  Every strategy reduces to *a permutation
   of the edge list plus a prefix width* (``prepare_sampling``), which
   is what makes the whole matrix sound: scatter-min sweeps are
   order-free and sweeping any edge subset is a valid min-mapping
   relaxation, so a sampled sweep is just a cheaper sound sweep and the
   fixed point is untouched.  On power-law / suite graphs a few cheap
   sampled sweeps are enough for one giant intermediate component to
   emerge.

2. **Skip-the-largest-component filter** — after the sampling phase, the
   most frequent current label (the largest intermediate component) is
   found on device (``largest_component_label``) and every edge both of
   whose endpoints contract into it is retired, à la ConnectIt/Afforest.

3. **Periodic active-edge contraction** — every ``compact_every``
   iterations the still-active edges are *contracted*: endpoints are
   rewritten to their depth-2 representatives ``L²[v]`` and self-loops of
   the contraction are retired by a stable partition into an
   ``[active | retired]`` edge layout with a device-resident ``active_m``
   count.  Subsequent sweeps and the early-convergence check touch only
   the active prefix (masked tiles under XLA; skipped grid steps in the
   label-blocked Pallas kernel via a scalar-prefetched live-chunk count).

Everything runs inside one ``lax.while_loop`` — edge arrays and
``active_m`` are loop state, compaction happens under ``lax.switch`` —
so there are **zero** host syncs, and the schedule composes with ``vmap``
(``solve_batch``) and per-shard with ``shard_map`` (``distributed``).

Why *contraction* and not mere dropping (DESIGN.md §10): retiring an edge
``(u, v)`` solely because its endpoint labels currently agree is unsound
here — the agreement is witnessed only by label *pointers*, and a later
scatter-min can redirect those pointers through a different part of the
component, stranding one side on a stale root (the seed's union-find
baseline never hits this because its unions are permanent).  Rewriting the
*surviving* edges to their current representatives keeps every
inter-supervertex adjacency in the edge list itself, so retired vertices
only ever hang off monotone pointer chains; a final pointer-jump
compression to the star-forest fixed point then yields labels bit-identical
to the uncompacted path (property-tested against the oracle in
``tests/test_frontier.py``).

The retired suffix keeps the edge arrays' static shape, so labels at the
fixed point are bit-identical to the uncompacted path while the counted
work (``edges_visited``) collapses from ``iterations × m`` to the sum of
per-sweep active counts.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro import tracing
from repro.connectivity import minmap as lab

# The deterministic sampling prefix is m // SAMPLE_PREFIX_DENOM edges
# (at least 1 — a zero-width prefix on a graph with m < DENOM edges
# would turn every sampling iteration into a no-op that burns the
# budget).  ConnectIt samples neighbours per vertex; an edge-list
# prefix is the order-free analogue and keeps the phase a pure static
# slice of the same arrays.
SAMPLE_PREFIX_DENOM = 4

# k-out/Afforest sampling: how many incident edges each vertex
# contributes to the sample by default (SolveOptions.sampling_k).
DEFAULT_SAMPLING_K = 2

# BFS/low-diameter-decomposition sampling: balls of this radius are
# grown around this many top-degree seed vertices; the sample is every
# edge with an endpoint inside a ball.
BFS_SAMPLE_SEEDS = 16
BFS_SAMPLE_ROUNDS = 4


def sample_prefix_m(n_edges: int) -> int:
    """Static size of the deterministic edge-prefix sample."""
    return max(1, n_edges // SAMPLE_PREFIX_DENOM)


def stable_partition(src: jax.Array, dst: jax.Array, keep: jax.Array
                     ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Stable two-way partition of an edge list into ``[keep | rest]``.

    O(m) via two prefix sums (the ``contract_edges`` trick): keepers land
    at their keep-rank, the rest after the last keeper at their
    rest-rank; both ranks are monotone in position, so relative order
    within each class is preserved.  Returns ``(src', dst', n_keep)``
    with ``n_keep`` an int32 scalar (traced-safe).
    """
    n_keep = jnp.sum(keep).astype(jnp.int32)
    kidx = jnp.cumsum(keep.astype(jnp.int32)) - 1
    ridx = n_keep + jnp.cumsum((~keep).astype(jnp.int32)) - 1
    dest = jnp.where(keep, kidx, ridx).astype(jnp.int32)
    out_s = jnp.zeros_like(src).at[dest].set(src)
    out_d = jnp.zeros_like(dst).at[dest].set(dst)
    return out_s, out_d, n_keep


def _occurrence_rank(x: jax.Array) -> jax.Array:
    """``rank[i]`` = how many earlier positions hold the same value as
    ``x[i]`` — i.e. the edge-list-order index of this occurrence among
    its value's occurrences.  Vectorised: stable argsort groups equal
    values (ties keep list order), a cummax over group starts recovers
    each group's base offset."""
    m = x.shape[0]
    if m == 0:
        return jnp.zeros((0,), jnp.int32)
    order = jnp.argsort(x)                       # stable in jax.numpy
    xs = x[order]
    idx = jnp.arange(m, dtype=jnp.int32)
    starts = jnp.concatenate(
        [jnp.ones((1,), bool), xs[1:] != xs[:-1]])
    group_start = jax.lax.cummax(jnp.where(starts, idx, 0))
    rank_sorted = idx - group_start
    return jnp.zeros((m,), jnp.int32).at[order].set(rank_sorted)


def _prepare_prefix(src, dst, n_vertices, k):
    """The deterministic edge prefix: identity permutation."""
    del n_vertices, k
    return src, dst, jnp.int32(sample_prefix_m(src.shape[0]))


def _prepare_kout(src, dst, n_vertices, k):
    """Afforest/k-out neighbour subgraph: each vertex's first ``k``
    incident edges (in edge-list order, either endpoint) are sampled.

    Sampled edges are stably partitioned to the front; the sample is the
    resulting prefix.  Low-degree vertices contribute everything they
    have, so on bounded-degree graphs (paths, grids with degree <= k)
    the sample is the whole edge list — exactly Afforest's behaviour.
    """
    del n_vertices
    m = src.shape[0]
    if m == 0:
        return src, dst, jnp.int32(0)
    sampled = (_occurrence_rank(src) < k) | (_occurrence_rank(dst) < k)
    out_s, out_d, sample_m = stable_partition(src, dst, sampled)
    # >= 1 whenever edges exist: rank 0 of any endpoint is always sampled
    return out_s, out_d, jnp.maximum(sample_m, jnp.int32(min(1, m)))


def _prepare_bfs(src, dst, n_vertices, k):
    """BFS/low-diameter-decomposition sample: grow balls of radius
    ``BFS_SAMPLE_ROUNDS`` around the ``BFS_SAMPLE_SEEDS`` highest-degree
    vertices; sample every edge with an endpoint in a ball.

    High-degree seeds are where the giant component condenses first, so
    the sampled subgraph gives the post-sampling largest-component
    filter the best target per swept edge.
    """
    del k
    m = src.shape[0]
    if m == 0:
        return src, dst, jnp.int32(0)
    deg = (jnp.zeros((n_vertices,), jnp.int32).at[src].add(1)
           .at[dst].add(1))
    _, seeds = jax.lax.top_k(deg, min(BFS_SAMPLE_SEEDS, n_vertices))
    reached = jnp.zeros((n_vertices,), jnp.int32).at[seeds].set(1)

    def grow(_, r):
        hit = jnp.maximum(r[src], r[dst])
        return r.at[src].max(hit).at[dst].max(hit)

    reached = jax.lax.fori_loop(0, BFS_SAMPLE_ROUNDS, grow, reached)
    sampled = (reached[src] | reached[dst]) > 0
    out_s, out_d, sample_m = stable_partition(src, dst, sampled)
    # the top-degree seed has an incident edge whenever m > 0
    return out_s, out_d, jnp.maximum(sample_m, jnp.int32(min(1, m)))


@dataclasses.dataclass(frozen=True)
class SamplingStrategy:
    """One pluggable sampling phase (ConnectIt's sampling axis).

    ``prepare(src, dst, n_vertices, k) -> (src', dst', sample_m)``
    returns the edge list *permuted* so the sampled edges form the
    leading ``sample_m`` positions (``sample_m`` is an int32 scalar, may
    be traced).  Reducing every sampler to permutation + prefix is the
    soundness argument of DESIGN.md §16: the main loop then treats any
    strategy exactly like the original prefix sampler, and a sampled
    sweep is just a sound min-mapping sweep over fewer edges.
    """

    name: str
    prepare: Callable[[jax.Array, jax.Array, int, int],
                      Tuple[jax.Array, jax.Array, jax.Array]]


_SAMPLING_REGISTRY: Dict[str, SamplingStrategy] = {}


def register_sampling_strategy(strategy: SamplingStrategy
                               ) -> SamplingStrategy:
    _SAMPLING_REGISTRY[strategy.name] = strategy
    return strategy


register_sampling_strategy(SamplingStrategy("prefix", _prepare_prefix))
register_sampling_strategy(SamplingStrategy("kout", _prepare_kout))
register_sampling_strategy(SamplingStrategy("bfs", _prepare_bfs))

# canonical order, used by SolveOptions validation and the bench matrix
SAMPLING_STRATEGIES = ("prefix", "kout", "bfs")


def get_sampling_strategy(name: str) -> SamplingStrategy:
    if name not in _SAMPLING_REGISTRY:
        raise ValueError(
            f"unknown sampling_strategy {name!r}; one of "
            f"{tuple(sorted(_SAMPLING_REGISTRY))}")
    return _SAMPLING_REGISTRY[name]


def prepare_sampling(name: str, src: jax.Array, dst: jax.Array,
                     n_vertices: int, k: int = DEFAULT_SAMPLING_K
                     ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Permute ``(src, dst)`` so the strategy's sample is the leading
    prefix; returns ``(src', dst', sample_m)``."""
    if k < 1:
        raise ValueError(f"sampling k must be >= 1, got {k}")
    prepare = get_sampling_strategy(name).prepare
    with jax.named_scope(tracing.SAMPLING_PREP):
        return prepare(src, dst, n_vertices, k)


def largest_component_label(L: jax.Array, n_vertices: int) -> jax.Array:
    """Label of the largest *current* intermediate component (device mode).

    The most frequent value of ``L`` — ConnectIt's "skip the largest
    component" target.  O(n) bincount + argmax, run once after the
    sampling phase.
    """
    return jnp.argmax(jnp.bincount(L, length=n_vertices)).astype(L.dtype)


def contract_edges(
    L: jax.Array,
    src: jax.Array,
    dst: jax.Array,
    active_m: jax.Array,
    *,
    only_label: Optional[jax.Array] = None,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """One contraction step: relabel active edges, retire self-loops.

    Active edges (positions ``< active_m``) are rewritten to their depth-2
    representatives ``(L²[u], L²[v])`` — sound on its own, since the
    representative is in the same component as the vertex — and then
    partitioned (stably, so the sweep order of survivors is preserved)
    into ``[active | retired]``.  ``only_label`` restricts retirement to
    self-loops of that label (the post-sampling largest-component filter);
    ``None`` retires every self-loop of the contraction.

    Returns ``(src', dst', active_m')`` with ``active_m' <= active_m`` —
    retired edges are never re-examined, so the count is monotonically
    non-increasing across compactions.
    """
    m = src.shape[0]
    pos = jnp.arange(m, dtype=active_m.dtype)
    act = pos < active_m
    rs = jnp.where(act, L[L[src]], src)
    rd = jnp.where(act, L[L[dst]], dst)
    if only_label is None:
        retire = rs == rd
    else:
        retire = (rs == only_label) & (rd == only_label)
    retire = retire | ~act
    # Stable two-way partition in O(m) via two prefix sums — replaces the
    # previous stable argsort (O(m log m) and the dominant term of every
    # compaction, ROADMAP open item 1).  Shared with the sampling
    # strategies' sampled-edges-first reorder (stable_partition).
    out_s, out_d, n_keep = stable_partition(rs, rd, ~retire)
    return out_s, out_d, n_keep.astype(active_m.dtype)


def masked_converged_early(
    L: jax.Array, src: jax.Array, dst: jax.Array, active_m: jax.Array
) -> jax.Array:
    """Paper §III-B2 early-convergence predicate over the active prefix.

    Retired edges are inside their components by construction, so only
    the ``active_m``-edge prefix can still violate the predicate; with an
    empty frontier the solve is converged by definition.
    """
    with jax.named_scope(tracing.CONVERGE_CHECK):
        pos = jnp.arange(src.shape[0], dtype=active_m.dtype)
        lw, lv = L[src], L[dst]
        bad = (lw != lv) | (lw != L[lw]) | (lv != L[lv])
        return ~jnp.any(bad & (pos < active_m))


def frontier_limit(it: jax.Array, active_m: jax.Array, sample_m: jax.Array,
                   sampling: int) -> jax.Array:
    """Per-iteration sweep bound: sample prefix first, live frontier after.

    Shared by the single-device engine and the per-shard ``shard_map``
    step (``connectivity.distributed``) so the two schedules cannot drift.
    """
    if sampling > 0:
        return jnp.where(it < sampling, jnp.minimum(sample_m, active_m),
                         active_m)
    return active_m


def gate_sampling_done(done: jax.Array, it: jax.Array,
                       sampling: int) -> jax.Array:
    """Pass-through: convergence may fire during the sampling phase.

    The old gate (``done & (it >= sampling)``) held convergence hostage
    to the full sampling budget on the reasoning that "the sample sees
    only part of the graph" — but :func:`masked_converged_early` checks
    the §III-B2 predicate over the *entire* active prefix, not just the
    swept sample, so ``done`` already certifies the full fixed point.
    The gate only burned ``sampling - it`` no-op iterations on graphs
    that converge during sampling (an already-connected warm start, or
    an edgeless graph whose every sampled sweep is empty).  Kept as a
    named seam so the masked, staged, and distributed engines document
    the shared rationale at their one convergence site.
    """
    del it, sampling
    return done


def apply_compaction(
    L: jax.Array,
    src: jax.Array,
    dst: jax.Array,
    active_m: jax.Array,
    it1: jax.Array,
    *,
    sampling: int,
    compact_every: int,
    n_vertices: int,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """The compaction schedule for iteration ``it1`` (post-increment).

    The largest-component filter fires once, right after the sampling
    phase; general contraction fires every ``compact_every`` iterations
    thereafter.  One ``lax.switch`` keeps the O(m log m) partition out of
    non-compacting iterations — on the *unbatched* path; under ``vmap``
    (``solve_batch``) the batched branch index lowers the switch to
    compute-all-and-select, so batched adaptive lanes pay the partition
    every iteration (fleets of small graphs, so the sort is small too —
    but batched adaptive is a counter/TPU win, not a CPU wall-time one).
    Shared by the single-device engine and the per-shard distributed
    step.
    """
    do_lc = (it1 == sampling) if sampling > 0 else jnp.array(False)
    if compact_every > 0:
        do_gen = (it1 > sampling) & ((it1 - sampling) % compact_every == 0)
    else:
        do_gen = jnp.array(False)

    def no_compact(args):
        _, e_src, e_dst, am = args
        return e_src, e_dst, am

    def compact_largest(args):
        lbl, e_src, e_dst, am = args
        c_hat = largest_component_label(lbl, n_vertices)
        return contract_edges(lbl, e_src, e_dst, am, only_label=c_hat)

    def compact_general(args):
        lbl, e_src, e_dst, am = args
        return contract_edges(lbl, e_src, e_dst, am)

    idx = jnp.where(do_lc, 1, jnp.where(do_gen, 2, 0))
    with jax.named_scope(tracing.COMPACT):
        return jax.lax.switch(
            idx, [no_compact, compact_largest, compact_general],
            (L, src, dst, active_m))


def compress_full(L: jax.Array) -> jax.Array:
    """Pointer-jump to the star-forest fixed point.

    The classic (uncompacted) loop ends one jump from a star forest, but
    vertices retired by contraction hang off pointer *chains* whose depth
    is unbounded by the convergence predicate (only active edges are
    checked), so the adaptive path compresses to the fixed point — the
    O(log depth) rounds run once, after the main loop.
    """
    with jax.named_scope(tracing.COMPRESS):
        return jax.lax.while_loop(
            lambda lbl: ~lab.is_star_forest(lbl),
            lambda lbl: lab.pointer_jump(lbl, rounds=1),
            L,
        )


class FrontierState(NamedTuple):
    """Loop state of the work-adaptive fixpoint."""

    L: jax.Array
    it: jax.Array          # int32 iteration counter
    done: jax.Array        # bool, on device
    src: jax.Array         # [m] edge sources, [active | retired] layout
    dst: jax.Array         # [m] edge destinations, same layout
    active_m: jax.Array    # int32 count of live prefix edges
    visited: jax.Array     # float32 cumulative edges swept (perf counter)


def adaptive_fixpoint(
    src: jax.Array,
    dst: jax.Array,
    L0: jax.Array,
    step: Callable[[jax.Array, jax.Array, jax.Array, jax.Array, jax.Array],
                   jax.Array],
    *,
    n_vertices: int,
    sampling: int,
    compact_every: int,
    max_iters: int,
    active_m0: Optional[jax.Array] = None,
    sample_m0: Optional[jax.Array] = None,
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array, jax.Array]:
    """Run ``step`` to the connectivity fixed point, work-adaptively.

    Args:
      src, dst: int32[m] edge list (each undirected edge once).
      L0: int32[n] initial labels (identity or a warm start).
      step: ``step(L, it, src, dst, limit) -> L_new`` — one sweep over the
        first ``limit`` edges of ``(src, dst)``; backends realise the
        limit as masked tiles (XLA) or skipped grid steps (Pallas).
      n_vertices: static vertex count.
      sampling: number of prefix-sample iterations (static, >= 0).
      compact_every: contraction cadence in iterations (static; 0 = only
        the post-sampling largest-component filter, if any).
      max_iters: iteration budget (static).
      active_m0: initial live-prefix count (traced int32 scalar; default
        the full ``m``).  Callers passing fewer assert the suffix is
        already intra-component under ``L0`` — e.g. self-loop padding, or
        the streaming engine's pre-retired padded tail
        (``connectivity.streaming``) — so it is never swept *and never
        counted* in ``edges_visited``.
      sample_m0: sample-prefix width (traced int32 scalar; default the
        deterministic ``sample_prefix_m``).  A non-default
        :class:`SamplingStrategy` passes the width of its sampled-first
        permutation here (``prepare_sampling``).

    Returns:
      ``(labels, iterations, converged, active_m, edges_visited)``.
      ``edges_visited`` is a float32 counter (documented approximate above
      2**24 per-increment precision; exact for every suite graph here).
    """
    m = src.shape[0]
    sample_m = (jnp.int32(sample_prefix_m(m)) if sample_m0 is None
                else jnp.asarray(sample_m0, jnp.int32))

    def cond(s: FrontierState):
        return (~s.done) & (s.it < max_iters)

    def body(s: FrontierState):
        limit = frontier_limit(s.it, s.active_m, sample_m, sampling)
        L = step(s.L, s.it, s.src, s.dst, limit)
        visited = s.visited + limit.astype(jnp.float32)
        done = gate_sampling_done(
            masked_converged_early(L, s.src, s.dst, s.active_m),
            s.it, sampling)
        it1 = s.it + 1
        src2, dst2, active2 = apply_compaction(
            L, s.src, s.dst, s.active_m, it1, sampling=sampling,
            compact_every=compact_every, n_vertices=n_vertices)
        return FrontierState(L=L, it=it1, done=done, src=src2, dst=dst2,
                             active_m=active2, visited=visited)

    init = FrontierState(
        L=L0,
        it=jnp.int32(0),
        done=jnp.array(False),
        src=src,
        dst=dst,
        active_m=(jnp.int32(m) if active_m0 is None
                  else jnp.asarray(active_m0, jnp.int32)),
        visited=jnp.float32(0),
    )
    out = jax.lax.while_loop(cond, body, init)
    return compress_full(out.L), out.it, out.done, out.active_m, out.visited
