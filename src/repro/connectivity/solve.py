"""The unified connectivity facade: ``solve(graph, options) -> ComponentResult``.

One entry point for every algorithm family the reproduction implements
(all Contour variants, FastSV, label propagation, host-side Rem
union-find, and the ``shard_map`` distributed path), with:

* **typed options** — :class:`~repro.connectivity.options.SolveOptions`
  replaces per-algorithm string kwargs;
* **automatic dispatch** — ``backend="auto"`` resolves kernels through
  ``plan_contour_kernel``; setting ``SolveOptions.mesh`` routes a Contour
  solve through the distributed path;
* **warm starts** — pass a previous :class:`ComponentResult` (or a raw
  label array) to continue after ``Graph.add_edges``: min-mapping labels
  only decrease, so the old fixed point is a correct head start
  (``minmap.resolve_init_labels``).

Example::

    from repro import solve, SolveOptions, Graph

    result = solve(graph)                               # Contour C-2
    result = solve(graph, SolveOptions(algorithm="fastsv"))
    result = solve(graph, algorithm="contour", variant="C-m")

    bigger = graph.add_edges(new_src, new_dst)
    result2 = solve(bigger, warm_start=result)          # incremental
"""
from __future__ import annotations

import itertools
from typing import Optional, Union

import jax
import jax.numpy as jnp

from repro import tracing
from repro.connectivity import minmap
from repro.connectivity import planner as _planner
from repro.connectivity.options import SolveOptions
from repro.runtime.recovery import is_transient_error
from repro.connectivity.registry import SolverSpec, get_solver
from repro.connectivity.result import ComponentResult
from repro.graphs.structs import Graph

# Solver families that route sweeps through the kernel dispatch layer and
# therefore carry a resolved ExecutionPlan (recorded in provenance).
# "oocore" additionally gets the VMEM-derived streaming chunk bucket
# stamped into the plan (solvers.resolve_backend_plan).
_PLANNED_SOLVERS = ("contour", "distributed", "oocore")

# numbers the solve() calls of the process in their trace spans
_SOLVE_CALLS = itertools.count()


def resolve_warm_start(warm_start, n_vertices: int):
    """Normalise a warm start to a label array (or None).

    Accepts a previous :class:`ComponentResult`, any array-like of labels,
    or None.  Only the *shape class* is checked here; length/validity
    normalisation (graph growth, the ``L[v] <= v`` invariant, the
    too-long error) lives in :func:`minmap.resolve_init_labels` — the
    single validator every solver funnels through.
    """
    del n_vertices  # length is validated by minmap.resolve_init_labels
    if warm_start is None:
        return None
    if isinstance(warm_start, ComponentResult):
        if warm_start.is_batched:
            raise ValueError(
                "warm_start is a batched ComponentResult; unstack() it or "
                "use solve_batch")
        warm_start = warm_start.labels
    labels = jnp.asarray(warm_start)
    if labels.ndim != 1:
        raise ValueError(
            f"warm_start labels must be 1-D, got shape {labels.shape}")
    # Negative-label check at the facade: device solvers reach
    # minmap.resolve_init_labels only from inside jit, where the values
    # are tracers and the eager check cannot fire.
    minmap.check_labels_nonnegative(labels)
    return labels


def solver_output(out):
    """Normalise a registry solver's return to a uniform 4-tuple.

    Solvers return ``(labels, iterations, converged)`` or the same plus a
    float32 ``edges_visited`` work counter (see ``registry``); both
    ``solve`` and ``solve_batch`` funnel through here.  A host-driven
    solver may append a 5th element — a static tuple of provenance
    strings (e.g. the out-of-core round decay) — which ``solve`` merges
    into the result's provenance and batching ignores (it cannot cross a
    ``vmap``).
    """
    labels, iterations, converged = out[:3]
    edges_visited = out[3] if len(out) > 3 else None
    return labels, iterations, converged, edges_visited


def make_result(labels, iterations, converged, edges_visited=None,
                batch_sizes=None, provenance=None) -> ComponentResult:
    """Canonical dtype normalisation into a :class:`ComponentResult`.

    The single constructor funnel for ``solve``, ``solve_batch`` and the
    streaming engine's ``snapshot()``, so the result dtypes (int32
    iterations, bool converged, float32 work counter) cannot drift between
    entry points.  ``provenance`` is the static degradation/recovery
    event tuple (empty/None = clean solve).
    """
    return ComponentResult(
        labels=labels,
        iterations=jnp.asarray(iterations, jnp.int32),
        converged=jnp.asarray(converged, bool),
        batch_sizes=batch_sizes,
        edges_visited=(None if edges_visited is None
                       else jnp.asarray(edges_visited, jnp.float32)),
        provenance=(tuple(provenance) if provenance else None))


def _resolve(options: Optional[SolveOptions],
             overrides) -> tuple[SolveOptions, SolverSpec]:
    """Validate options and pick the solver (mesh-aware)."""
    opts = options if options is not None else SolveOptions()
    if not isinstance(opts, SolveOptions):
        raise TypeError(
            f"options must be SolveOptions, got {type(opts).__name__}")
    if overrides:
        opts = opts.replace(**overrides)
    opts.validate()
    spec = get_solver(opts.algorithm)
    if opts.mesh is not None:
        if not spec.supports_mesh:
            raise ValueError(
                f"solver {spec.name!r} does not run on a mesh; use "
                "algorithm='contour' (or 'distributed')")
        if spec.name == "contour":
            # automatic single-device vs mesh dispatch
            spec = get_solver("distributed")
    opts = opts.replace(
        variant=spec.validate_variant(opts.variant),
        # registry default is the single source of per-solver budgets
        max_iters=(spec.default_max_iters if opts.max_iters is None
                   else opts.max_iters),
    )
    return opts, spec


def solve(
    graph: Graph,
    options: Optional[SolveOptions] = None,
    *,
    warm_start: Union[None, ComponentResult, jax.Array] = None,
    **overrides,
) -> ComponentResult:
    """Solve connectivity on ``graph``; returns a :class:`ComponentResult`.

    Args:
      graph: edge-list :class:`Graph` (each undirected edge once).
      options: a :class:`SolveOptions`; defaults to Contour C-2 with
        automatic kernel dispatch.
      warm_start: previous labels (array or :class:`ComponentResult`) to
        continue from — e.g. after :meth:`Graph.add_edges`.  Overrides
        ``options.warm_start``.
      **overrides: per-call :class:`SolveOptions` field overrides, e.g.
        ``solve(g, algorithm="fastsv")``.

    Returns:
      :class:`ComponentResult` with min-vertex-id ``labels``, the solver's
      ``iterations`` count, and a ``converged`` flag (each solver's own
      fixed-point test from its final loop state — the paper's §III-B2
      predicate for the min-mapping family; False iff the ``max_iters``
      budget ran out first).
    """
    with jax.profiler.TraceAnnotation(tracing.SOLVE,
                                      call=next(_SOLVE_CALLS)):
        opts, spec = _resolve(options, overrides)
        init = resolve_warm_start(
            warm_start if warm_start is not None else opts.warm_start,
            graph.n_vertices)
        if init is not None and not spec.supports_warm_start:
            raise ValueError(f"solver {spec.name!r} does not support warm "
                             "starts")
        plan = None
        if spec.name in _PLANNED_SOLVERS:
            # Resolve the execution plan once at the facade (pinned >
            # tuning cache for "auto" > heuristic tables) and pin it into
            # the options so the solver, the provenance record and any
            # retry all see the same plan.
            from repro.connectivity.solvers import resolve_backend_plan
            with jax.profiler.TraceAnnotation(tracing.SOLVE_PLAN):
                _, plan = resolve_backend_plan(graph.n_vertices, graph.n_edges,
                                               opts)
            opts = opts.replace(plan=plan)
        provenance = []
        try:
            with jax.profiler.TraceAnnotation(tracing.SOLVE_DISPATCH):
                out = spec.fn(graph, opts, init)
            if plan is not None:
                provenance.append(plan.provenance_entry())
        except Exception as exc:
            # Graceful degradation (DESIGN.md §12): a failed non-XLA kernel
            # launch falls back to the XLA reference path instead of failing
            # the request.  Caller bugs (ValueError/TypeError/...), kernels
            # that fail to lower or compile, and injected SimulatedFaults
            # propagate untouched.
            backend = (plan.backend if plan is not None
                       and opts.backend == "auto" else opts.backend)
            if (not opts.kernel_fallback or backend == "xla"
                    or spec.runs_on != "device"
                    or not is_transient_error(exc)):
                raise
            try:
                # demote this size bucket to XLA in the tuning cache — with a
                # TTL, so the failed backend is retried/retuned later instead
                # of being pinned out forever
                _planner.record_kernel_failure(
                    graph.n_vertices, graph.n_edges, failed_backend=backend)
            except Exception:
                pass  # a cache write must never break the degradation path
            retry_opts = opts.replace(backend="xla", plan=None)
            with jax.profiler.TraceAnnotation(tracing.SOLVE_DISPATCH):
                out = spec.fn(graph, retry_opts, init)
            provenance.append(
                f"kernel_fallback:{backend}->xla "
                f"({type(exc).__name__}: {str(exc)[:120]})")
            if spec.name in _PLANNED_SOLVERS:
                from repro.connectivity.solvers import resolve_backend_plan
                _, retry_plan = resolve_backend_plan(
                    graph.n_vertices, graph.n_edges, retry_opts)
                provenance.append(
                    retry_plan.replace(origin="fallback").provenance_entry())
        with jax.profiler.TraceAnnotation(tracing.SOLVE_RESULT):
            labels, iterations, converged, edges_visited = solver_output(out)
            if len(out) > 4 and out[4]:
                provenance.extend(out[4])
            return make_result(labels, iterations, converged, edges_visited,
                               provenance=provenance)
