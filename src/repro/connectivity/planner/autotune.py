"""The measuring autotuner: time candidate plans, cache the winner.

ConnectIt's central observation (PAPERS.md) is that the right dispatch
choice is a *per-graph-family measurement*, not a table.  This module
makes the plan layer measured:

* :func:`candidate_plans` enumerates a bounded set of (backend,
  label_block, chunk, compact-schedule) configs for a graph size — the
  heuristic prior is always candidate zero;
* :func:`autotune` times each candidate on the caller's actual graph
  (best-of-k wall clock through the real ``solve`` facade, so the
  measurement includes exactly what a user pays) and persists the winner
  to the on-disk cache (``planner.cache``) keyed by
  (platform, n-bucket, m-bucket);
* **hysteresis**: a non-heuristic candidate is committed only when it
  beats the heuristic by more than ``margin`` (default 5%) — near-ties
  resolve to the prior, so the bench ``autotune_gate``'s re-measurement
  cannot flip a coin-toss into a regression.

Tuning never happens implicitly: ``solve()`` only *reads* the cache
(through ``planner.resolve_plan``).  Timing is injectable (``measure=``)
so the decision logic is unit-testable without wall-clock noise.
"""
from __future__ import annotations

import time
from typing import Callable, Dict, List, Optional, Tuple

import jax

from repro.connectivity.planner import cache as _cache
from repro.connectivity.planner.heuristics import heuristic_plan
from repro.connectivity.planner.plan import ExecutionPlan

# Fallback demotions expire after this long; past it the bucket resolves
# back to the heuristic (or a fresh tuning) and the failed backend is
# retried — a flaky kernel launch must not pin XLA forever.
FALLBACK_TTL_S = 3600.0


def plan_label(plan: ExecutionPlan) -> str:
    """Short human key for timing tables."""
    return (f"{plan.backend}/{plan.compact_schedule}"
            f"/lb{plan.label_block}/cu{plan.chunk_updates}"
            f"{'/fused' if plan.fuse_relabel else ''}")


def candidate_plans(n_vertices: int, m_edges: int,
                    platform: Optional[str] = None) -> List[ExecutionPlan]:
    """Bounded candidate set; the heuristic prior is always first."""
    platform = platform or jax.default_backend()
    base = heuristic_plan(n_vertices, m_edges, platform)
    cands = [base]

    def add(p: ExecutionPlan):
        if not any(p.config_equal(c) for c in cands):
            cands.append(p)

    for schedule in ("masked", "staged"):
        add(base.replace(compact_schedule=schedule))
    if platform == "tpu":
        # the blocked kernel at a tile-size neighbourhood, in shapes
        # Mosaic compiles (the combine cost per update is ∝ label_block;
        # bin padding waste is ∝ blocks·chunk)
        for lb in (1024, 2048, 4096):
            for cu in (1024, 4096):
                add(base.replace(backend="pallas_blocked", label_block=lb,
                                 chunk_updates=cu, fuse_relabel=False))
        if base.fuse_relabel:
            add(base.replace(fuse_relabel=False))
    return cands


def _measure_solve(graph, plan: ExecutionPlan, opts,
                   repeats: int = 3) -> float:
    """Best-of-k wall clock of ``solve`` under a pinned plan."""
    from repro.connectivity.solve import solve  # lazy: avoid import cycle

    pinned = opts.replace(plan=plan.replace(origin="pinned"),
                          backend=plan.backend)

    def run():
        res = solve(graph, pinned)
        res.labels.block_until_ready()

    run()                                   # warmup / compile
    best = float("inf")
    for _ in range(max(1, repeats)):
        t0 = time.perf_counter()
        run()
        best = min(best, time.perf_counter() - t0)
    return best


def autotune(
    graph,
    opts=None,
    *,
    platform: Optional[str] = None,
    repeats: int = 3,
    margin: float = 0.05,
    measure: Optional[Callable] = None,
    cache_path: Optional[str] = None,
    write: bool = True,
) -> Tuple[ExecutionPlan, Dict[str, float]]:
    """Measure candidates on ``graph``; cache and return the winner.

    Returns ``(plan, timings)`` where ``plan`` has ``origin="tuned"`` and
    ``timings`` maps :func:`plan_label` to best-of-k seconds.  ``measure``
    overrides the timing function (``measure(graph, plan, opts) -> s``)
    for deterministic tests; ``write=False`` skips the cache write.
    """
    from repro.connectivity.options import SolveOptions  # lazy

    platform = platform or jax.default_backend()
    if opts is None:
        # the workload shape tuning certifies: the work-adaptive schedule
        # (where masked-vs-staged matters) on the default variant
        opts = SolveOptions(sampling=2, compact_every=2)
    if measure is None:
        measure = lambda g, p, o: _measure_solve(g, p, o, repeats=repeats)

    n, m = graph.n_vertices, graph.n_edges
    cands = candidate_plans(n, m, platform)
    timings: Dict[str, float] = {}
    best_plan, best_t = None, float("inf")
    for p in cands:
        t = float(measure(graph, p, opts))
        timings[plan_label(p)] = t
        if t < best_t:
            best_plan, best_t = p, t
    heur = cands[0]
    heur_t = timings[plan_label(heur)]
    # hysteresis: commit a non-prior config only on a clear win
    if not best_plan.config_equal(heur) and best_t >= heur_t * (1 - margin):
        best_plan, best_t = heur, heur_t
    tuned = best_plan.replace(origin="tuned")
    if write:
        _cache.store(n, m, platform, tuned, time_s=best_t, timings=timings,
                     origin="tuned", path=cache_path)
    return tuned, timings


def record_kernel_failure(
    n_vertices: int,
    m_edges: int,
    platform: Optional[str] = None,
    *,
    failed_backend: str = "",
    ttl_s: float = FALLBACK_TTL_S,
    cache_path: Optional[str] = None,
) -> ExecutionPlan:
    """Demote a bucket to XLA after a kernel-launch failure — with a TTL.

    The resilience fallback path (``solve``/streaming) calls this so the
    *next* solve in the bucket resolves straight to XLA instead of
    re-failing; once ``ttl_s`` lapses the entry expires and the bucket
    retunes, so a transient failure never pins XLA permanently.
    """
    platform = platform or jax.default_backend()
    plan = ExecutionPlan(backend="xla",
                         interpret=(platform != "tpu"),
                         compact_schedule=heuristic_plan(
                             n_vertices, m_edges, platform).compact_schedule,
                         origin="fallback")
    _cache.store(n_vertices, m_edges, platform, plan, origin="fallback",
                 ttl_s=ttl_s, path=cache_path,
                 timings={"demoted_from": failed_backend} if failed_backend
                 else None)
    return plan
