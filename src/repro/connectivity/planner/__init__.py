"""Execution-plan layer: how a connectivity solve actually runs.

The seed buried dispatch policy in ``kernels.contour_mm.ops`` as a frozen
``KernelPlan`` plus hand-tuned heuristic tables.  This package lifts it
into a first-class, *measured* layer:

* :mod:`~repro.connectivity.planner.plan` — :class:`ExecutionPlan`, the
  hashable value threaded (as a jit-static argument) through every solver
  path: backend, tile sizes, frontier compaction schedule
  (masked-in-loop vs physically staged), relabel fusion, and its origin
  (heuristic / tuned / pinned / fallback).
* :mod:`~repro.connectivity.planner.heuristics` — the cold-start tables
  (the autotuner's prior, and the only policy used under ``jit`` tracing
  or when the cache is unusable).
* :mod:`~repro.connectivity.planner.autotune` /
  :mod:`~repro.connectivity.planner.cache` — the measuring autotuner and
  its on-disk cache keyed by (platform, n-bucket, m-bucket).
* :mod:`~repro.connectivity.planner.vmem` — per-platform VMEM budget and
  the whole-L ceiling derived from it (was a hard-coded constant).
* :mod:`~repro.connectivity.planner.staged` — the physically-sliced
  staged frontier driver (the grid really shrinks with the frontier).
* :mod:`~repro.connectivity.planner.costmodel` — the ``solver="auto"``
  strategy cost model (pinned > fitted from the bench artifact >
  heuristic), DESIGN.md §16.

:func:`resolve_plan` is the single resolution point::

    pinned plan argument  >  tuning cache (only for backend="auto")
                          >  heuristic tables

The cache is consulted *only* when the caller left the backend on
``"auto"``: an explicit backend choice is a statement of intent (and the
bench HLO-identity gate depends on forced backends staying deterministic).
"""
from __future__ import annotations

from typing import Optional

import jax

from repro.connectivity.planner import cache
from repro.connectivity.planner.costmodel import (
    ENV_BENCH_ARTIFACT,
    StrategyChoice,
    resolve_strategy,
)
from repro.connectivity.planner.autotune import (
    autotune,
    candidate_plans,
    plan_label,
    record_kernel_failure,
)
from repro.connectivity.planner.heuristics import (
    OOCORE_BYTES_PER_EDGE,
    SINGLE_TILE_MAX_N,
    STAGED_MIN_EDGES,
    XLA_SWEEP_MIN_EDGES,
    heuristic_plan,
    oocore_chunk_bucket,
)
from repro.connectivity.planner.plan import (
    BACKENDS,
    COMPACT_SCHEDULES,
    ORIGINS,
    ExecutionPlan,
    next_pow2,
    plan_key,
    size_bucket,
)
from repro.connectivity.planner.vmem import (
    ENV_VMEM_BYTES,
    vmem_budget_bytes,
    whole_l_vmem_ceiling,
)

__all__ = [
    "BACKENDS",
    "COMPACT_SCHEDULES",
    "ENV_BENCH_ARTIFACT",
    "ENV_VMEM_BYTES",
    "StrategyChoice",
    "resolve_strategy",
    "OOCORE_BYTES_PER_EDGE",
    "ORIGINS",
    "SINGLE_TILE_MAX_N",
    "STAGED_MIN_EDGES",
    "XLA_SWEEP_MIN_EDGES",
    "ExecutionPlan",
    "autotune",
    "cache",
    "candidate_plans",
    "heuristic_plan",
    "next_pow2",
    "oocore_chunk_bucket",
    "plan_key",
    "plan_label",
    "record_kernel_failure",
    "resolve_plan",
    "size_bucket",
    "vmem_budget_bytes",
    "whole_l_vmem_ceiling",
]


def resolve_plan(
    n_vertices: int,
    m_edges: int,
    *,
    backend: str = "auto",
    plan=None,
    platform: Optional[str] = None,
    use_cache: bool = True,
) -> ExecutionPlan:
    """Resolve the :class:`ExecutionPlan` for one solve.

    ``plan`` pinned by the caller wins outright (lifted from a legacy
    ``KernelPlan`` if needed).  Otherwise, with ``backend="auto"``, a
    valid non-expired tuning-cache entry for this size bucket is used;
    on a miss — or with any *forced* backend — the heuristic tables
    decide (with the forced backend substituted in).
    """
    if plan is not None:
        return ExecutionPlan.from_kernel_plan(plan)
    platform = platform or jax.default_backend()
    if backend == "auto":
        if use_cache:
            cached = cache.lookup(n_vertices, m_edges, platform)
            if cached is not None:
                return cached
        return heuristic_plan(n_vertices, m_edges, platform)
    p = heuristic_plan(n_vertices, m_edges, platform)
    if p.backend != backend:
        # forced off the table's choice: pallas kernels off-TPU only run
        # interpreted, and the interpret flag must follow the platform
        p = p.replace(backend=backend,
                      interpret=(platform != "tpu" and
                                 backend.startswith("pallas")))
    return p
