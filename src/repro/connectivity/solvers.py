"""Registered solver families wrapping every existing implementation.

Five families, one signature (DESIGN.md §9 maps them onto the paper):

* ``contour``           — paper §III-B, all variants (Alg. 1 + §III-B4),
  any ``kernels.contour_mm`` backend, single device.
* ``distributed``       — paper §III-B over a device mesh (§IV Arkouda
  mapping): ``shard_map`` edge-sharded Contour C-2.  ``solve()`` routes
  ``contour`` here automatically when ``SolveOptions.mesh`` is set.
* ``fastsv``            — paper §III-C, the Shiloach-Vishkin family
  (Zhang, Azad & Hu).
* ``label_propagation`` — paper §I/§V traversal-family strawman.
* ``union_find``        — paper §III-C ConnectIt stand-in (host-side
  Rem's algorithm with splicing).
* ``oocore``            — out-of-core multi-round contraction
  (DESIGN.md §15): edges stream from host memory chunk by chunk, so
  problem size is decoupled from device memory.
* ``auto``              — ConnectIt-style measured dispatch (DESIGN.md
  §16): the planner cost model picks the (solver family, sampling
  strategy) per graph and delegates; the choice lands in provenance.
"""
from __future__ import annotations

import jax

from repro.connectivity import contour as _contour
from repro.connectivity import distributed as _distributed
from repro.connectivity import fastsv as _fastsv
from repro.connectivity import lp as _lp
from repro.connectivity import oocore as _oocore
from repro.connectivity import planner as _planner
from repro.connectivity import unionfind as _unionfind
from repro.connectivity.planner import staged as _staged
from repro.connectivity.registry import (SolverSpec, get_solver,
                                         register_solver)
from repro.graphs import stats as _stats
from repro.graphs.generators import ArrayChunks

# Registry names that resolve to the out-of-core solver (and therefore
# need ExecutionPlan.chunk_bucket stamped at plan resolution).
_OOCORE_NAMES = ("oocore", "out_of_core")


def sweep_edges(n_edges: int, opts) -> int:
    """Edges one sweep of a solve of ``n_edges`` edges covers: all of them
    on one device; on a mesh (``opts.mesh``), where each device sweeps
    only its own, one shard's block of them."""
    if getattr(opts, "mesh", None) is None:
        return n_edges
    return _distributed.shard_edges(n_edges, opts.mesh, opts.edge_axes)


def resolve_backend_plan(n_vertices: int, n_edges: int, opts):
    """Concrete (backend, plan) for a solve of ``n_edges`` edges, keyed
    on the edges one sweep covers (:func:`sweep_edges`).

    Resolution goes through the execution-plan layer
    (:func:`repro.connectivity.planner.resolve_plan`): a plan pinned in
    ``opts.plan`` wins; otherwise ``backend="auto"`` consults the tuning
    cache and falls back to the heuristic tables, while an explicit
    backend takes the tables with that backend substituted.  Always
    returns a concrete backend and an :class:`planner.ExecutionPlan`
    (legacy ``KernelPlan`` pins are lifted).  For the out-of-core solver
    the plan additionally carries the VMEM-derived streaming chunk
    bucket (``chunk_bucket``), unless the pinned plan already set one.
    """
    n_edges = sweep_edges(n_edges, opts)
    plan = _planner.resolve_plan(n_vertices, n_edges, backend=opts.backend,
                                 plan=opts.plan)
    backend = plan.backend if opts.backend == "auto" else opts.backend
    if (getattr(opts, "algorithm", None) in _OOCORE_NAMES
            and plan.chunk_bucket == 0):
        plan = plan.replace(chunk_bucket=_planner.oocore_chunk_bucket(
            n_edges,
            vmem_limit_bytes=opts.vmem_limit_bytes,
            requested=opts.oocore_chunk_edges))
    return backend, plan


def _sampling_provenance(opts):
    """Static provenance entry naming the sampling strategy in effect."""
    if opts.sampling <= 0:
        return ()
    return (f"sampling_strategy:{opts.sampling_strategy or 'prefix'}",)


def _contour_solver(graph, opts, init_labels):
    backend, plan = resolve_backend_plan(graph.n_vertices, graph.n_edges,
                                         opts)
    variant = opts.variant or "C-2"
    strategy = opts.sampling_strategy or "prefix"
    adaptive = opts.sampling > 0 or opts.compact_every > 0
    if (adaptive and variant != "C-Syn"
            and plan.compact_schedule == "staged"
            and not isinstance(graph.src, jax.core.Tracer)):
        # physically staged frontier: host-driven stage loop, edge arrays
        # really shrink.  Unavailable under an enclosing trace (vmap'd
        # solve_batch, user jit) — those keep the masked in-loop schedule,
        # which is bit-identical at the fixed point.
        out = _staged.staged_adaptive_labels(
            graph.src, graph.dst, graph.n_vertices, init_labels,
            variant=variant,
            max_iters=opts.max_iters,
            warmup=opts.warmup,
            async_compress=opts.async_compress,
            backend=backend,
            plan=plan,
            sampling=opts.sampling,
            compact_every=opts.compact_every,
            sampling_strategy=strategy,
            sampling_k=opts.sampling_k,
            vmem_limit_bytes=opts.vmem_limit_bytes,
        )
        return (*out, _sampling_provenance(opts))
    out = _contour.contour_labels(
        graph.src, graph.dst, graph.n_vertices, init_labels,
        variant=variant,
        max_iters=opts.max_iters,
        warmup=opts.warmup,
        async_compress=opts.async_compress,
        backend=backend,
        plan=plan,
        sampling=opts.sampling,
        compact_every=opts.compact_every,
        sampling_strategy=strategy,
        sampling_k=opts.sampling_k,
        vmem_limit_bytes=opts.vmem_limit_bytes,
    )
    return (*out, _sampling_provenance(opts))


def _distributed_solver(graph, opts, init_labels):
    if opts.mesh is None:
        raise ValueError(
            "the 'distributed' solver needs SolveOptions.mesh (a "
            "jax.sharding.Mesh); for single-device solves use "
            "algorithm='contour'")
    if (opts.sampling_strategy or "prefix") != "prefix":
        raise ValueError(
            "the 'distributed' solver samples a deterministic per-shard "
            "edge prefix; sampling_strategy "
            f"{opts.sampling_strategy!r} is single-device only (it "
            "permutes the global edge list, which would break the static "
            "shard layout) — use algorithm='contour'")
    backend, plan = resolve_backend_plan(graph.n_vertices, graph.n_edges,
                                         opts)
    return _distributed.distributed_contour(
        graph, opts.mesh,
        edge_axes=tuple(opts.edge_axes),
        local_rounds=opts.local_rounds,
        max_iters=opts.max_iters,
        async_compress=opts.async_compress,
        backend=backend,
        plan=plan,
        init_labels=init_labels,
        sampling=opts.sampling,
        compact_every=opts.compact_every,
    )


def _fastsv_solver(graph, opts, init_labels):
    return _fastsv.fastsv_labels(graph.src, graph.dst, graph.n_vertices,
                                 init_labels,
                                 max_iters=opts.max_iters)


def _lp_solver(graph, opts, init_labels):
    return _lp.label_propagation_labels(graph.src, graph.dst,
                                        graph.n_vertices, init_labels,
                                        max_iters=opts.max_iters)


def _union_find_solver(graph, opts, init_labels):
    return _unionfind.rem_labels(graph.src, graph.dst, graph.n_vertices,
                                 init_labels=init_labels)


def _oocore_solver(graph, opts, init_labels):
    if isinstance(graph.src, jax.core.Tracer):
        raise ValueError(
            "the 'oocore' solver is host-driven (it streams edge chunks "
            "between rounds) and cannot run under an enclosing trace; "
            "call solve() eagerly or use algorithm='contour'")
    backend, plan = resolve_backend_plan(graph.n_vertices, graph.n_edges,
                                         opts)
    bucket = plan.chunk_bucket or _planner.oocore_chunk_bucket(
        graph.n_edges, vmem_limit_bytes=opts.vmem_limit_bytes,
        requested=opts.oocore_chunk_edges)
    src, dst, n = graph.to_numpy()
    chunks = ArrayChunks(src, dst, n, bucket)
    return _oocore.oocore_labels(chunks, opts, init_labels=init_labels)


def _auto_solver(graph, opts, init_labels):
    """ConnectIt-style measured dispatch (DESIGN.md §16).

    Resolves a (solver family, sampling strategy) via the planner cost
    model — pinned ``SolveOptions`` fields > fitted bench-artifact model
    > heuristic table — then delegates to the chosen registered solver.
    The choice (and the delegate's plan) is returned in the static
    provenance element so every auto solve records what ran and why.
    """
    skew = None
    if not isinstance(graph.src, jax.core.Tracer):
        # degree skew needs values, not shapes; under an enclosing trace
        # the model falls back to its size-only features
        np_src, np_dst, n = graph.to_numpy()
        skew = _stats.degree_skew(np_src, np_dst, n)
    choice = _planner.resolve_strategy(
        graph.n_vertices, graph.n_edges,
        degree_skew=skew,
        pinned_strategy=opts.sampling_strategy,
        pinned_variant=opts.variant)
    delegate = get_solver(choice.solver)
    d_opts = opts.replace(
        algorithm=choice.solver,
        variant=choice.variant,
        sampling_strategy=choice.sampling_strategy,
        # explicit schedule knobs on the options win over the model's
        sampling=opts.sampling or choice.sampling,
        compact_every=opts.compact_every or choice.compact_every,
    )
    out = tuple(delegate.fn(graph, d_opts, init_labels))
    provenance = [choice.provenance_entry()]
    from repro.connectivity.solve import _PLANNED_SOLVERS  # lazy: cycle
    if choice.solver in _PLANNED_SOLVERS:
        _, plan = resolve_backend_plan(graph.n_vertices, graph.n_edges,
                                       d_opts)
        provenance.append(plan.provenance_entry())
    if len(out) > 4 and out[4]:
        provenance.extend(out[4])
    base = out[:4] if len(out) >= 4 else (*out[:3], None)
    return (*base, tuple(provenance))


CONTOUR = register_solver(SolverSpec(
    name="contour",
    fn=_contour_solver,
    variants=_contour.VARIANTS + ("C-<h>",),
    default_variant="C-2",
    default_max_iters=100_000,
    supports_mesh=True,          # via automatic routing to 'distributed'
    supports_streaming=True,     # any async variant (C-Syn rejected)
    paper_ref="§III-B (Alg. 1, variants §III-B4)",
))

DISTRIBUTED = register_solver(SolverSpec(
    name="distributed",
    fn=_distributed_solver,
    aliases=("contour_distributed",),
    variants=("C-2",),
    default_variant="C-2",
    default_max_iters=10_000,
    supports_batch=False,        # shard_map placement, not vmappable
    supports_mesh=True,
    supports_streaming=True,     # per-shard delta contraction, C-2 only
    paper_ref="§III-B over §IV's distributed mapping",
))

FASTSV = register_solver(SolverSpec(
    name="fastsv",
    fn=_fastsv_solver,
    default_max_iters=256,
    paper_ref="§III-C (FastSV / Shiloach-Vishkin family)",
))

LABEL_PROPAGATION = register_solver(SolverSpec(
    name="label_propagation",
    fn=_lp_solver,
    aliases=("lp",),
    default_max_iters=100_000,
    paper_ref="§I/§V (traversal-family baseline)",
))

UNION_FIND = register_solver(SolverSpec(
    name="union_find",
    fn=_union_find_solver,
    aliases=("connectit", "rem"),
    default_max_iters=1,
    supports_batch=False,        # host-side sequential loop
    runs_on="host",
    paper_ref="§III-C (ConnectIt stand-in: Rem's union-find)",
))

AUTO = register_solver(SolverSpec(
    name="auto",
    fn=_auto_solver,
    variants=_contour.VARIANTS + ("C-<h>",),
    default_variant=None,        # the cost model picks unless pinned
    default_max_iters=100_000,
    supports_streaming=True,
    paper_ref="ConnectIt strategy-matrix dispatch (DESIGN.md §16)",
))

OOCORE = register_solver(SolverSpec(
    name="oocore",
    fn=_oocore_solver,
    aliases=("out_of_core",),
    variants=_contour.VARIANTS + ("C-<h>",),
    default_variant="C-2",
    default_max_iters=100_000,
    supports_batch=False,        # host-driven round loop, not vmappable
    paper_ref="§III-B streamed per Behnezhad et al. / ConnectIt "
              "multi-round contraction (DESIGN.md §15)",
))
