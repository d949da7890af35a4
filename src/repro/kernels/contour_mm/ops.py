"""Jit'd wrappers for the contour_mm kernels: backend dispatch + autotune.

Three device backends realise the same MM^h sweep (DESIGN.md §3):

* ``"xla"``           — synchronous scatter-min (`lab.mm_relax`); the only
  backend that *compiles* on a CPU host (Pallas TPU kernels cannot), and
  what `repro.connectivity.distributed` defaults to.
* ``"pallas"``        — the seed fused in-VMEM asynchronous kernel
  (`kernel.mm2_pallas`): whole ``L`` VMEM-resident (ceiling n ≈ 3M),
  scalar sequential inner loop, 2-order only.  Kept as the
  deterministic-async reference; interpret mode only — it does not
  compile for TPU, so a compiled request raises ``ValueError``.
* ``"pallas_blocked"`` — the label-blocked vectorized kernel
  (`blocked.binned_scatter_min_pallas`): edges are reduced to an update
  stream, radix-binned by ``target // label_block`` on device, and one
  grid step per update chunk runs with ``L`` *tiled* via BlockSpec — no
  vertex ceiling, VPU-vectorized scatter-min, any order.  Per sweep it is
  bit-exact equal to ``"xla"``.

``"auto"`` picks per graph size and platform via :func:`plan_contour_kernel`
— the shared dispatch/autotune layer used by `core.contour`,
`core.distributed` and `benchmarks.connectivity`.

:func:`contour_cc_fixpoint` iterates any backend to the connectivity fixed
point inside a single ``lax.while_loop`` — the convergence flag stays on
device, so there are **zero** per-iteration host syncs (the seed version
pulled ``bool(converged_early(...))`` across the device boundary every
iteration).  ``sampling``/``compact_every`` switch it to the work-adaptive
frontier contraction schedule (``connectivity.frontier``, DESIGN.md §10);
every sweep accepts an ``edge_limit`` frontier bound, which the blocked
kernel realises as skipped grid steps via a dead-bin sort plus a
scalar-prefetched live-chunk count.
"""
from __future__ import annotations

import dataclasses
import functools
import warnings
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.connectivity import frontier as fr
from repro.connectivity import minmap as lab
from repro.connectivity.planner import vmem as _vmem
from repro.connectivity.planner.heuristics import heuristic_plan
from repro.graphs.structs import Graph
from repro.kernels.contour_mm.blocked import (_round_up,
                                              binned_scatter_min_pallas,
                                              fused_relax_pallas)
from repro.kernels.contour_mm.kernel import mm2_pallas

BACKENDS = ("auto", "xla", "pallas", "pallas_blocked")

# Above this vertex count a fully VMEM-resident int32 L no longer fits the
# platform's VMEM budget alongside edge blocks (kernel.py header) — the
# scalar "pallas" backend is invalid and blocking is mandatory.  Derived
# from the queried/declared VMEM budget (planner.vmem), overridable via
# SolveOptions.vmem_limit_bytes or $REPRO_VMEM_BYTES; this module-level
# snapshot exists for back-compat imports (the dispatch path re-derives).
WHOLE_L_VMEM_CEILING = _vmem.whole_l_vmem_ceiling()


@dataclasses.dataclass(frozen=True)
class KernelPlan:
    """Resolved backend + tile sizes for one graph size (hashable/static).

    Legacy shape — the execution-plan layer
    (:class:`repro.connectivity.planner.ExecutionPlan`) supersedes it,
    adding the compaction schedule, relabel fusion and plan origin.  Kept
    so pinned plans in existing call sites keep working; every consumer
    accepts either (``ExecutionPlan.from_kernel_plan`` lifts this).
    """

    backend: str                # concrete: "xla" | "pallas" | "pallas_blocked"
    block_edges: int = 512      # edge block of the scalar pallas kernel
    label_block: int = 1024     # L tile height of the blocked kernel
    chunk_updates: int = 1024   # update-stream chunk of the blocked kernel
    interpret: bool = False     # Pallas interpreter mode (CPU validation)


def plan_contour_kernel(
    n_vertices: int,
    n_edges: int,
    platform: Optional[str] = None,
) -> KernelPlan:
    """Deprecated: use :func:`repro.connectivity.planner.resolve_plan`.

    Thin shim over the planner's heuristic tables, kept for one
    deprecation cycle.  It returns the legacy :class:`KernelPlan` (no
    schedule/fusion fields) and never consults the tuning cache.
    """
    warnings.warn(
        "plan_contour_kernel is deprecated; use "
        "repro.connectivity.planner.resolve_plan (measured, cached) or "
        "planner.heuristic_plan (the same tables, richer plan)",
        DeprecationWarning, stacklevel=2)
    p = heuristic_plan(n_vertices, n_edges, platform)
    return KernelPlan(backend=p.backend, block_edges=p.block_edges,
                      label_block=p.label_block,
                      chunk_updates=p.chunk_updates, interpret=p.interpret)


def _pad_edges(src, dst, multiple: int):
    m = src.shape[0]
    target = _round_up(m, multiple)
    pad = target - m
    if pad:
        src = jnp.concatenate([src, jnp.zeros((pad,), src.dtype)])
        dst = jnp.concatenate([dst, jnp.zeros((pad,), dst.dtype)])
    return src, dst


# The sweep's gather phase lives next to mm_relax so the two realisations
# can never drift apart (bit-exactness is load-bearing — see ref.py).
mm_update_stream = lab.mm_update_stream


def mm_relax_backend(
    L: jax.Array,
    src: jax.Array,
    dst: jax.Array,
    *,
    order: int = 2,
    backend: str = "auto",
    block_edges: Optional[int] = None,
    label_block: Optional[int] = None,
    chunk_updates: Optional[int] = None,
    interpret: Optional[bool] = None,
    platform: Optional[str] = None,
    edge_limit: Optional[jax.Array] = None,
    fuse: Optional[bool] = None,
    vmem_limit_bytes: Optional[int] = None,
) -> jax.Array:
    """One MM^order sweep on the chosen backend (trace-level, not jitted).

    ``None`` tile parameters resolve from the planner's heuristic tables
    (``planner.heuristic_plan``), including ``interpret`` (False on TPU,
    True elsewhere — validation mode).  The tables only — never the
    tuning cache: this resolution happens inside jitted fixpoints, where
    it must stay a pure function of (shape, platform) so compiled
    programs (and the bench HLO-identity gate) are reproducible.  Cache
    hits are applied by ``planner.resolve_plan`` at the solve facade.
    ``platform`` overrides the plan's target platform for AOT lowering
    from a different host (e.g. ``.lower()``-ing a TPU program on a CPU
    dry-run host).  This is the single entry every layer routes sweeps
    through.

    ``fuse`` opts the blocked backend into the fused relabel+scatter-min
    kernel (one Pallas pass instead of XLA gathers + radix binning +
    scatter kernel); it applies in the single-tile order-2 regime and
    falls back to the binned pipeline otherwise.  ``vmem_limit_bytes``
    overrides the platform VMEM budget behind the scalar kernel's
    whole-L ceiling.

    ``edge_limit`` is the work-adaptive frontier bound (a traced int32
    scalar): only the first ``edge_limit`` edges contribute updates.  The
    XLA and scalar-pallas backends mask the suffix to self-loop no-ops
    (same shapes, so the program stays jit-stable); the blocked kernel
    routes the suffix's update stream into a dead tail bin and skips those
    grid steps outright (``blocked.binned_scatter_min_pallas``).
    """
    if backend not in BACKENDS:
        raise ValueError(f"unknown backend {backend!r}; one of {BACKENDS}")
    n = int(L.shape[0])
    m = int(src.shape[0])
    plan = heuristic_plan(n, m, platform)
    if backend == "auto":
        backend = plan.backend
    block_edges = plan.block_edges if block_edges is None else block_edges
    label_block = plan.label_block if label_block is None else label_block
    chunk_updates = (plan.chunk_updates if chunk_updates is None
                     else chunk_updates)
    interpret = plan.interpret if interpret is None else interpret
    fuse = plan.fuse_relabel if fuse is None else fuse

    edge_mask = None
    if edge_limit is not None:
        edge_mask = jnp.arange(m, dtype=jnp.int32) < edge_limit

    if backend == "xla":
        if edge_mask is not None:
            # self-loops at vertex 0 are min-mapping no-ops (structs.Graph
            # padding uses the same trick)
            src = jnp.where(edge_mask, src, 0)
            dst = jnp.where(edge_mask, dst, 0)
        return lab.mm_relax(L, src, dst, order)
    if backend == "pallas":
        if not interpret:
            raise ValueError(
                "the scalar 'pallas' kernel does not compile for TPU "
                "(Mosaic cannot store scalars to VMEM); use "
                "'pallas_blocked' or 'xla'")
        if order != 2:
            raise ValueError(
                "the scalar 'pallas' kernel is 2-order only; use "
                "'pallas_blocked' or 'xla' for order != 2")
        ceiling = _vmem.whole_l_vmem_ceiling(platform,
                                             vmem_bytes=vmem_limit_bytes)
        if n > ceiling:
            raise ValueError(
                f"n_vertices={n} exceeds the scalar 'pallas' kernel's "
                f"whole-L VMEM ceiling ({ceiling}); use 'pallas_blocked' "
                "(label-tiled, no ceiling) or 'xla', or raise the budget "
                f"via SolveOptions.vmem_limit_bytes / ${_vmem.ENV_VMEM_BYTES}")
        if edge_mask is not None:
            src = jnp.where(edge_mask, src, 0)
            dst = jnp.where(edge_mask, dst, 0)
        src_p, dst_p = _pad_edges(src, dst, block_edges)
        return mm2_pallas(src_p, dst_p, L, block_edges=block_edges,
                          interpret=interpret)
    # pallas_blocked
    if fuse and order == 2 and max(128, _round_up(n, 128)) <= label_block:
        # single-tile regime: one Pallas pass does gathers (relabel) and
        # all four scatter-min combines — no update-stream materialisation,
        # no radix binning, no argsort
        return fused_relax_pallas(
            L, src, dst, chunk_edges=chunk_updates, interpret=interpret,
            edge_limit=edge_limit)
    t, v = lab.mm_update_stream(L, src, dst, order)
    valid = None
    if edge_mask is not None:
        # the stream is 2*order concatenated [m] segments (targets per
        # Definition 3); each inherits the per-edge liveness
        valid = jnp.tile(edge_mask, 2 * order)
    return binned_scatter_min_pallas(
        L, t, v, label_block=label_block, chunk_updates=chunk_updates,
        interpret=interpret, valid=valid)


@functools.partial(
    jax.jit,
    static_argnames=("backend", "order", "block_edges", "label_block",
                     "chunk_updates", "interpret", "platform", "fuse"),
)
def contour_mm_step(
    src: jax.Array,
    dst: jax.Array,
    L: jax.Array,
    *,
    backend: str = "pallas",
    order: int = 2,
    block_edges: int = 512,
    label_block: Optional[int] = None,
    chunk_updates: Optional[int] = None,
    interpret: Optional[bool] = None,
    platform: Optional[str] = None,
    fuse: Optional[bool] = None,
) -> jax.Array:
    """One MM sweep over all edges. Returns the updated label array."""
    return mm_relax_backend(
        L, src, dst, order=order, backend=backend, block_edges=block_edges,
        label_block=label_block, chunk_updates=chunk_updates,
        interpret=interpret, platform=platform, fuse=fuse)


class _FixState(NamedTuple):
    L: jax.Array
    it: jax.Array          # int32 iteration counter
    done: jax.Array        # bool, lives on device across iterations


@functools.partial(
    jax.jit,
    static_argnames=("backend", "order", "block_edges", "label_block",
                     "chunk_updates", "interpret", "platform", "max_iters",
                     "sampling", "compact_every", "fuse"),
)
def contour_cc_fixpoint(
    graph: Graph,
    *,
    backend: str = "auto",
    order: int = 2,
    block_edges: int = 512,
    label_block: Optional[int] = None,
    chunk_updates: Optional[int] = None,
    interpret: Optional[bool] = None,
    platform: Optional[str] = None,
    max_iters: int = 10_000,
    sampling: int = 0,
    compact_every: int = 0,
    fuse: Optional[bool] = None,
):
    """Iterate the kernel to the connectivity fixed point, fully on device.

    A single ``lax.while_loop`` carries ``(L, it, done)``; the paper's
    early-convergence predicate (§III-B2) is evaluated on device and feeds
    the loop condition directly — no per-iteration device→host readback.
    (The jit around this function is itself the proof: a host-side
    ``bool(converged)`` would fail to trace.)  Returns
    (labels, n_iters, converged, edges_visited) — ``converged`` is the
    loop's own flag, False iff the ``max_iters`` budget ran out;
    ``edges_visited`` is a float32 work counter (``n_iters * m`` for the
    dense schedule).

    ``sampling`` / ``compact_every`` enable the work-adaptive frontier
    contraction schedule (``connectivity.frontier``): sample-prefix
    sweeps, the post-sampling largest-component filter, and periodic
    active-edge contraction — same single while loop, edge arrays and the
    ``active_m`` count carried as loop state.
    """
    L0 = jnp.arange(graph.n_vertices, dtype=graph.src.dtype)
    if sampling < 0 or compact_every < 0:
        raise ValueError("sampling and compact_every must be >= 0, got "
                         f"{sampling} / {compact_every}")

    if sampling > 0 or compact_every > 0:
        def step(L, it, src, dst, limit):
            del it
            L = mm_relax_backend(
                L, src, dst, order=order, backend=backend,
                block_edges=block_edges, label_block=label_block,
                chunk_updates=chunk_updates, interpret=interpret,
                platform=platform, edge_limit=limit, fuse=fuse)
            return lab.pointer_jump(L, rounds=1)

        L, it, done, _, visited = fr.adaptive_fixpoint(
            graph.src, graph.dst, L0, step,
            n_vertices=graph.n_vertices, sampling=sampling,
            compact_every=compact_every, max_iters=max_iters)
        return L, it, done, visited

    def cond(s: _FixState):
        return (~s.done) & (s.it < max_iters)

    def body(s: _FixState):
        L = mm_relax_backend(
            s.L, graph.src, graph.dst, order=order, backend=backend,
            block_edges=block_edges, label_block=label_block,
            chunk_updates=chunk_updates, interpret=interpret,
            platform=platform, fuse=fuse)
        L = lab.pointer_jump(L, rounds=1)
        done = lab.converged_early(L, graph.src, graph.dst)
        return _FixState(L=L, it=s.it + 1, done=done)

    out = jax.lax.while_loop(
        cond, body, _FixState(L=L0, it=jnp.int32(0), done=jnp.array(False)))
    # Interior vertices of padded/isolated chains may be one hop from the
    # star root (same as connectivity.contour's final compression).
    visited = out.it.astype(jnp.float32) * graph.n_edges
    return lab.pointer_jump(out.L, rounds=1), out.it, out.done, visited
