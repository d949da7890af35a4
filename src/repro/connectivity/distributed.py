"""Distributed (multi-pod) Contour connectivity via ``shard_map``.

Mapping of the paper's Arkouda/Chapel distribution onto a TPU mesh
(DESIGN.md §3/§4):

* the edge list is block-sharded across the data-parallel mesh axes
  (``pod`` × ``data``); padding uses self-loop edges which are no-ops for
  every min-mapping operator;
* the label array ``L`` is replicated per device (n × 4 B — even a
  2³⁰-vertex graph is a 4 GB replica, fine for 16 GB HBM chips; an
  all-to-all label-sharded variant is the documented scale-out path);
* each global round: every device relaxes its local edge shard (through
  the ``kernels.contour_mm`` backend dispatch, which realises the sweep
  the planner picks) and compresses, then one ``lax.pmin`` all-reduce
  merges label arrays — the collective is the *only* cross-device
  traffic;
* convergence: the paper's early-convergence predicate evaluated on local
  edges, AND-reduced across devices.

Beyond-paper optimisation (§Perf, hillclimb #3): ``local_rounds > 1`` runs
k relax+compress rounds on the local shard between all-reduces.  Labels
decrease monotonically toward the same fixed point regardless of staleness,
so correctness is unaffected, while collective bytes per convergence drop
by ~k× on diameter-bound graphs.

``init_labels`` warm-starts the replicated label array from a previous
solve — the only change to the round structure is the initial replica.

``sampling`` / ``compact_every`` enable the work-adaptive frontier
contraction (``repro.connectivity.frontier``) *per shard*: each device
samples a prefix of its local edge shard, retires its local edges into
the largest component after the sampling phase, and periodically contracts
its own active prefix — the shard-local edge arrays and ``active_m``
counts are loop state, so the schedule adds no collective traffic (the
per-round ``pmin`` stays the only cross-device exchange; the counted
``edges_visited`` is ``psum``-reduced inside the existing round).
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import jax_compat, tracing
from repro.connectivity import frontier as fr
from repro.connectivity import minmap as lab
from repro.graphs.structs import Graph
from repro.kernels.contour_mm import ops as mm_ops


class _State(NamedTuple):
    L: jax.Array
    it: jax.Array
    done: jax.Array


class _FrontierShardState(NamedTuple):
    L: jax.Array
    it: jax.Array
    done: jax.Array
    src: jax.Array         # local shard, [active | retired] layout
    dst: jax.Array
    active_m: jax.Array    # live count of this shard's prefix
    visited: jax.Array     # float32, psum-reduced (identical on all shards)


def _round_up(x: int, k: int) -> int:
    return (x + k - 1) // k * k


@functools.partial(
    jax.jit,
    static_argnames=("mesh", "edge_axes", "local_rounds", "max_iters",
                     "async_compress", "backend", "plan", "sampling",
                     "compact_every"),
)
def _distributed_fixpoint(
    src: jax.Array,
    dst: jax.Array,
    L0: jax.Array,
    n_active: jax.Array,
    *,
    mesh: jax.sharding.Mesh,
    edge_axes: tuple,
    local_rounds: int,
    max_iters: int,
    async_compress: int,
    backend: str,
    plan=None,
    sampling: int,
    compact_every: int,
):
    """Module-level jitted core of :func:`distributed_contour`.

    Module-level so the jit cache survives across calls: a streaming
    engine re-invoking the mesh path per micro-batch (same shapes, same
    statics) compiles once, not once per batch.  ``n_active`` is the real
    (pre-padding) edge count; padding is never counted in
    ``edges_visited`` on either schedule — the dense branch scales its
    ``iterations x m`` count by it, the adaptive branch clamps each
    shard's initial live prefix to its slice of it (matching the
    single-device ``active_m0`` path).
    """
    axis = tuple(edge_axes)
    n_shards = 1
    for a in axis:
        n_shards *= mesh.shape[a]
    m = src.shape[0]
    n = L0.shape[0]
    m_loc = m // n_shards
    adaptive = sampling > 0 or compact_every > 0

    edge_spec = P(axis if len(axis) > 1 else axis[0])
    lbl_spec = P()  # replicated

    # per-shard tile parameters come from the resolved execution plan when
    # the facade threads one down (None = the heuristic tables, as before)
    tile_kw = {}
    if plan is not None:
        tile_kw = dict(block_edges=plan.block_edges,
                       label_block=plan.label_block,
                       chunk_updates=plan.chunk_updates,
                       interpret=plan.interpret,
                       fuse=getattr(plan, "fuse_relabel", False))

    def body(src_in, dst_in, L0, n_act):
        def relax_rounds(L, src_loc, dst_loc, limit):
            for _ in range(local_rounds):
                L = mm_ops.mm_relax_backend(L, src_loc, dst_loc, order=2,
                                            backend=backend,
                                            edge_limit=limit, **tile_kw)
                L = lab.pointer_jump(L, rounds=async_compress)
            # the one collective of the round: elementwise min across shards
            with jax.named_scope(tracing.LABEL_ALLREDUCE):
                return jax.lax.pmin(L, axis)

        def all_shards_ok(ok_local):
            return jnp.bool_(jax.lax.pmin(ok_local.astype(jnp.int32), axis))

        if not adaptive:
            def cond(s: _State):
                return (~s.done) & (s.it < max_iters)

            def step(s: _State):
                L = relax_rounds(s.L, src_in, dst_in, None)
                ok = all_shards_ok(lab.converged_early(L, src_in, dst_in))
                return _State(L=L, it=s.it + 1, done=ok)

            out = jax.lax.while_loop(
                cond, step,
                _State(L=L0, it=jnp.int32(0), done=jnp.array(False)))
            # dense sweeps physically touch the whole padded array (the
            # self-loops are no-ops), but the counter reports real edges
            # only — same contract as the adaptive branch
            visited = (out.it.astype(jnp.float32) * local_rounds
                       * n_act.astype(jnp.float32))
            return out.L, out.it, out.done, visited

        sample_m = jnp.int32(fr.sample_prefix_m(m_loc))

        # this shard's slice of the real-edge prefix: the global layout is
        # [real | padding] and P(axis) block-shards contiguously with the
        # first axis major, so shard i holds [i*m_loc, (i+1)*m_loc)
        shard_idx = jnp.int32(0)
        for a in axis:
            shard_idx = shard_idx * mesh.shape[a] + jax.lax.axis_index(a)
        active0 = jnp.clip(n_act - shard_idx * m_loc, 0,
                           m_loc).astype(jnp.int32)

        def cond(s: _FrontierShardState):
            return (~s.done) & (s.it < max_iters)

        def step(s: _FrontierShardState):
            limit = fr.frontier_limit(s.it, s.active_m, sample_m, sampling)
            L = relax_rounds(s.L, s.src, s.dst, limit)
            visited = s.visited + local_rounds * jax.lax.psum(
                limit.astype(jnp.float32), axis)
            ok = fr.gate_sampling_done(
                all_shards_ok(
                    fr.masked_converged_early(L, s.src, s.dst, s.active_m)),
                s.it, sampling)
            it1 = s.it + 1
            # L is replicated post-pmin, so every shard agrees on the
            # largest component and contracts its own edge shard against
            # the same schedule (shared with the single-device engine)
            src2, dst2, active2 = fr.apply_compaction(
                L, s.src, s.dst, s.active_m, it1, sampling=sampling,
                compact_every=compact_every, n_vertices=n)
            return _FrontierShardState(L=L, it=it1, done=ok, src=src2,
                                       dst=dst2, active_m=active2,
                                       visited=visited)

        out = jax.lax.while_loop(
            cond, step,
            _FrontierShardState(L=L0, it=jnp.int32(0), done=jnp.array(False),
                                src=src_in, dst=dst_in,
                                active_m=active0,
                                visited=jnp.float32(0)))
        return fr.compress_full(out.L), out.it, out.done, out.visited

    return jax_compat.shard_map(
        body,
        mesh=mesh,
        in_specs=(edge_spec, edge_spec, lbl_spec, lbl_spec),
        out_specs=(lbl_spec, lbl_spec, lbl_spec, lbl_spec),
    )(src, dst, L0, n_active)


def distributed_contour(
    graph: Graph,
    mesh: jax.sharding.Mesh,
    *,
    edge_axes: Sequence[str] = ("data",),
    local_rounds: int = 1,
    max_iters: int = 10_000,
    async_compress: int = 1,
    backend: str = "xla",
    plan=None,
    init_labels: Optional[jax.Array] = None,
    sampling: int = 0,
    compact_every: int = 0,
    n_active: Optional[int] = None,
):
    """Run Contour C-2 with edges sharded over ``edge_axes`` of ``mesh``.

    Returns ``(labels, n_global_rounds, converged, edges_visited)``.
    Works on any mesh whose
    ``edge_axes`` product divides the (padded) edge count — the production
    meshes in ``repro.launch.mesh`` and the multi-device CPU test mesh
    alike.  ``backend`` selects the per-shard sweep realisation through
    the shared ``kernels.contour_mm`` dispatch layer ("xla" scatter-min by
    default; "pallas_blocked" for the label-blocked TPU kernel; "auto" for
    the planner's choice).

    ``n_active`` overrides the real-edge count when the caller's graph is
    itself already padded with trailing self-loops (the streaming engine's
    pow2 buckets): edges past it are born retired in the adaptive
    schedule and never counted in ``edges_visited``.
    """
    if sampling < 0 or compact_every < 0:
        raise ValueError("sampling and compact_every must be >= 0, got "
                         f"{sampling} / {compact_every}")
    if n_active is None:
        n_active = graph.n_edges
    elif not 0 <= n_active <= graph.n_edges:
        raise ValueError(f"n_active={n_active} outside [0, "
                         f"{graph.n_edges}]")
    n_shards = 1
    for a in edge_axes:
        n_shards *= mesh.shape[a]
    g = graph.pad_edges(_round_up(max(graph.n_edges, n_shards), n_shards))
    axis = tuple(edge_axes)
    edge_spec = P(axis if len(axis) > 1 else axis[0])
    lbl_spec = P()

    src = jax.device_put(g.src, NamedSharding(mesh, edge_spec))
    dst = jax.device_put(g.dst, NamedSharding(mesh, edge_spec))
    L0 = jax.device_put(
        lab.resolve_init_labels(init_labels, g.n_vertices, g.src.dtype),
        NamedSharding(mesh, lbl_spec))
    return _distributed_fixpoint(
        src, dst, L0, jnp.int32(n_active),
        mesh=mesh, edge_axes=axis, local_rounds=local_rounds,
        max_iters=max_iters, async_compress=async_compress, backend=backend,
        plan=plan, sampling=sampling, compact_every=compact_every)


@functools.partial(
    jax.jit,
    static_argnames=("n_vertices", "mesh", "edge_axes", "local_rounds",
                     "max_iters", "check_every", "backend"),
)
def distributed_contour_step_fn(
    src,
    dst,
    n_vertices: int,
    mesh: jax.sharding.Mesh,
    edge_axes: tuple = ("data",),
    local_rounds: int = 1,
    max_iters: int = 10_000,
    check_every: int = 1,
    backend: str = "xla",
):
    """jit-compilable entry used by the dry-run/roofline harness.

    Identical math to :func:`distributed_contour`, but takes pre-sharded
    arrays so it can be ``.lower().compile()``-ed against
    ``ShapeDtypeStruct`` inputs on the production mesh.

    ``check_every`` is the beyond-paper convergence-check cadence: the
    paper's early check (§III-B2) gathers L at every edge endpoint each
    iteration (an O(m) gather + a scalar all-reduce); checking every k-th
    round removes that traffic from the other k-1 rounds at the cost of
    up to k-1 extra (cheap) relaxation rounds after the fixed point.
    """
    axis = tuple(edge_axes)
    edge_spec = P(axis if len(axis) > 1 else axis[0])

    def body(src_loc, dst_loc):
        L0 = jnp.arange(n_vertices, dtype=src_loc.dtype)

        def cond(s: _State):
            return (~s.done) & (s.it < max_iters)

        def step(s: _State):
            L = s.L
            for _ in range(local_rounds):
                L = mm_ops.mm_relax_backend(L, src_loc, dst_loc, order=2,
                                            backend=backend)
                L = lab.pointer_jump(L, rounds=1)
            with jax.named_scope(tracing.LABEL_ALLREDUCE):
                L = jax.lax.pmin(L, axis)
            if check_every <= 1:
                ok_local = lab.converged_early(L, src_loc, dst_loc)
                ok = jnp.bool_(jax.lax.pmin(ok_local.astype(jnp.int32), axis))
            else:
                def do_check(_):
                    ok_local = lab.converged_early(L, src_loc, dst_loc)
                    return jnp.bool_(
                        jax.lax.pmin(ok_local.astype(jnp.int32), axis))
                ok = jax.lax.cond(
                    (s.it + 1) % check_every == 0, do_check,
                    lambda _: jnp.array(False), operand=None)
            return _State(L=L, it=s.it + 1, done=ok)

        out = jax.lax.while_loop(
            cond, step, _State(L=L0, it=jnp.int32(0), done=jnp.array(False))
        )
        return out.L, out.it

    return jax_compat.shard_map(
        body, mesh=mesh, in_specs=(edge_spec, edge_spec), out_specs=(P(), P())
    )(src, dst)
