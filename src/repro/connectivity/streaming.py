"""Streaming incremental connectivity over edge micro-batches.

:class:`StreamingConnectivity` turns the one-shot warm-start path
(``solve(bigger, warm_start=prev)``) into a first-class engine for the
online workloads ConnectIt targets (PAPERS.md): a stream of edge batches
arrives, component labels must stay queryable after every batch, and
re-solving from scratch per batch is unaffordable.  Three pieces:

* **Delta re-convergence on the supervertex graph.**  Between batches
  the label array is a star-forest fixed point of everything ingested so
  far.  A new batch is re-converged by sweeping *only the new edges*,
  warm-started, under the §10 frontier schedule of
  ``connectivity.frontier`` (which contracts batch edges as their
  endpoints merge) — per-batch work tracks the delta, not the
  accumulated ``m``.

  Soundness is load-bearing and subtle.  Sweeping the new edges with
  their *original* endpoints is **wrong**, even at MM order 2: two batch
  edges can target a shared non-root vertex ``w`` and its root ``r`` in
  the same synchronous sweep with different values ``z_w > z_r``, after
  which ``w`` has been redirected off ``r``'s chain and nothing — the
  old edges are never reswept — reconnects them (the engine's test suite
  pins this counterexample).  The engine therefore first **rewrites each
  batch edge to its endpoints' current roots** ``(L[u], L[v])``.  Every
  rewritten endpoint is then a *root* of the warm star forest, so the
  delta solve is literally ordinary Contour on the supervertex graph
  (vertices = current roots, edges = rewritten batch) started from the
  identity labelling of its vertex set — correct by the paper's own
  convergence theorem, for every variant.  Vertices not in the batch are
  untouched during the solve (all sweep targets and label values stay
  inside the root set) and still point at their old root, which the
  final pointer-jump compression resolves through the root's new chain.
  This mirrors how §10 contraction stays sound (rewrite-to-
  representatives) where plain edge dropping is not — DESIGN.md §11.

* **Ring-buffered edge store.**  Ingested edges land in a growable
  device-resident ring (capacity a power of two, amortised doubling,
  free space filled with self-loop no-op edges).  Batches are padded to
  power-of-two shapes, so both the append (one
  ``lax.dynamic_update_slice``) and the delta solve compile once per
  bucket size — jit-stable ingestion.  The store exists for
  ``graph()``/``resolve()`` (audit / repair); queries never touch it.

* **O(1) snapshots.**  Labels are always converged between batches, so
  ``snapshot()`` just wraps them in a :class:`ComponentResult` and
  ``same_component``/``component_of`` answer from the resident array —
  no re-solve, no per-query device work beyond one gather.

``SolveOptions.mesh`` shards each batch through
``distributed.distributed_contour`` — per-shard frontier contraction, the
per-round ``pmin`` staying the only collective — so the same engine
drives a pod-scale stream.

Counters (``iterations``, ``edges_visited``, ``converged``) accumulate as
device scalars: steady-state ingestion performs **zero** host syncs (the
eager endpoint-bounds check is host-side but runs on the caller's NumPy
input; pass ``validate=False`` to skip it for pre-validated device
streams).
"""
from __future__ import annotations

import functools
from typing import Optional, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro import tracing
from repro.connectivity import distributed as dist
from repro.connectivity import frontier as fr
from repro.connectivity import minmap as lab
from repro.connectivity import planner as _planner
from repro.connectivity.contour import _make_step
from repro.connectivity.options import SolveOptions
from repro.connectivity.result import ComponentResult
from repro.connectivity.solve import _resolve, make_result, \
    resolve_warm_start, solve
from repro.connectivity.solvers import resolve_backend_plan, sweep_edges
from repro.graphs.structs import Graph
from repro.runtime.recovery import FaultInjector, is_transient_error

# Smallest edge-store capacity / batch padding bucket.  Power of two so
# amortised doubling keeps the number of distinct compiled shapes
# logarithmic in the stream length.
MIN_CAPACITY = 64


def next_pow2(x: int) -> int:
    """Smallest power of two >= max(x, 1)."""
    return 1 << max(0, x - 1).bit_length()


@functools.partial(
    jax.jit,
    static_argnames=("variant", "backend", "plan", "warmup",
                     "async_compress", "sampling", "compact_every",
                     "max_iters"),
)
def delta_converge(
    src: jax.Array,
    dst: jax.Array,
    labels: jax.Array,
    n_active: jax.Array,
    *,
    variant: str = "C-2",
    backend: str = "xla",
    plan=None,
    warmup: int = 2,
    async_compress: int = 1,
    sampling: int = 0,
    compact_every: int = 1,
    max_iters: int = 100_000,
):
    """Re-converge ``labels`` after a new edge micro-batch.

    The pure jit-compiled core of :class:`StreamingConnectivity`: rewrite
    the batch ``(src, dst)`` to its endpoints' current roots (see the
    module docstring for why that rewrite carries the soundness of the
    whole engine), sweep its first ``n_active`` edges warm-started from
    ``labels`` — which must be a star-forest fixed point of everything
    before the batch — under the work-adaptive frontier schedule, and
    return ``(labels', iterations, converged, edges_visited)`` with
    ``labels'`` compressed back to a star forest.

    Composes with ``jax.vmap`` for fleets of parallel streams (each lane
    carries its own labels and batch; ``n_active`` may differ per lane).
    """
    # supervertex rewrite: labels is a star forest, so L[u] is u's root
    with jax.named_scope(tracing.SUPERVERTEX_REWRITE):
        src = labels[src]
        dst = labels[dst]
    step = _make_step(variant, warmup, async_compress, backend, plan)
    L, it, done, _, visited = fr.adaptive_fixpoint(
        src, dst, labels, step,
        n_vertices=labels.shape[0],
        sampling=sampling,
        compact_every=compact_every,
        max_iters=max_iters,
        active_m0=n_active)
    return L, it, done, visited


@functools.partial(jax.jit, static_argnames=("pad_k",))
def _pad_batch(src: jax.Array, dst: jax.Array, pad_k: int):
    """Pad a batch to its bucket size with self-loop no-op edges."""
    with jax.named_scope(tracing.PAD):
        k = src.shape[0]
        fill = jnp.zeros((pad_k - k,), jnp.int32)
        return (jnp.concatenate([src.astype(jnp.int32), fill]),
                jnp.concatenate([dst.astype(jnp.int32), fill]))


@functools.partial(jax.jit, donate_argnums=(0,))
def _ring_write(buf: jax.Array, chunk: jax.Array, offset: jax.Array):
    """Write ``chunk`` into ``buf`` at ``offset`` (one compiled program
    per (capacity, bucket) shape pair).

    ``buf`` is donated: the caller immediately rebinds the store to the
    result, so the append updates in place instead of copying the whole
    capacity every batch.
    """
    with jax.named_scope(tracing.STORE):
        return jax.lax.dynamic_update_slice(buf, chunk, (offset,))


class StreamingConnectivity:
    """Incremental connectivity engine over a stream of edge batches.

    Example::

        eng = StreamingConnectivity(n_vertices=1_000_000)
        for src, dst in edge_batches:
            eng.ingest(src, dst)
            eng.same_component(0, 42)       # O(1), no re-solve
        final = eng.snapshot()              # ComponentResult

    Args:
      n_vertices: initial vertex count (``ingest(..., n_vertices=...)``
        grows it later).
      options: a :class:`SolveOptions`; must name a streaming-capable
        solver (Contour, any async variant — the supervertex rewrite
        makes every MM order sound; only the Alg.-1-verbatim ``C-Syn``
        is rejected).  ``mesh`` routes every batch through the
        ``shard_map`` distributed path.  If neither ``sampling`` nor
        ``compact_every`` is set, the engine defaults to
        ``compact_every=1`` so merged batch edges retire immediately.
      warm_start: labels (or a :class:`ComponentResult`) to seed from —
        e.g. a previous engine's :meth:`snapshot`.  Compressed to a star
        forest on entry.
      min_capacity: initial edge-store capacity (rounded up to a power
        of two).
      store_edges: keep every ingested edge in the device-resident store
        (enables :meth:`graph` and :meth:`resolve`).  ``False`` bounds
        the engine's memory at O(n) for indefinite streams — the labels
        are a lossless summary of the partition, so queries and delta
        solves never need the history.
      fault_injector: optional
        :class:`~repro.runtime.recovery.FaultInjector` consulted inside
        :meth:`ingest` at sites ``"pre"`` (before the delta solve) and
        ``"post_write"`` (after the ring-buffer write, before the
        commit) — the chaos-test hook proving ingest atomicity and
        bit-exact crash recovery (DESIGN.md §12).
      **overrides: per-field :class:`SolveOptions` overrides, as for
        ``solve()``.
    """

    # the checkpointable state (see state_dict); a stable key set is the
    # restore contract, so bump thoughtfully
    _STATE_KEYS = ("labels", "src", "dst", "m", "n", "n_cap", "n_batches",
                   "iterations", "converged", "edges_visited",
                   "store_edges")

    def __init__(
        self,
        n_vertices: int,
        options: Optional[SolveOptions] = None,
        *,
        warm_start: Union[None, ComponentResult, jax.Array] = None,
        min_capacity: int = MIN_CAPACITY,
        store_edges: bool = True,
        fault_injector: Optional[FaultInjector] = None,
        **overrides,
    ):
        opts, spec = _resolve(options, overrides)
        if not spec.supports_streaming:
            raise ValueError(
                f"solver {spec.name!r} does not support streaming; use "
                "algorithm='contour' (delta resweeps are a minimum-mapping "
                "property)")
        if opts.variant == "C-Syn":
            raise ValueError(
                "C-Syn is the Alg.-1-verbatim reference and rejects the "
                "frontier schedule the streaming engine is built on; use "
                "C-2/C-m (any async variant — the supervertex rewrite "
                "makes every order sound, see DESIGN.md §11)")
        if opts.sampling == 0 and opts.compact_every == 0:
            # the delta IS the frontier: contract merged batch edges away
            # every iteration by default
            opts = opts.replace(compact_every=1)
        self._opts = opts
        self._spec = spec
        self._n = int(n_vertices)

        # the label array is held at pow2 *capacity*, like the edge store:
        # vertices in [logical n, capacity) are identity-labelled isolated
        # singletons no real edge can touch (bounds-checked against the
        # logical n), so growth within capacity changes no array shape and
        # triggers no recompile — per-doc growers (StreamingDedup) pay one
        # compile per capacity doubling, not per batch
        self._n_cap = next_pow2(max(self._n, 1))
        # same fallback as solve(): the kwarg wins, else the options field
        init = resolve_warm_start(
            warm_start if warm_start is not None else opts.warm_start,
            self._n)
        L0 = lab.resolve_init_labels(init, self._n_cap, jnp.int32)
        # engine invariant: labels between batches are a star-forest fixed
        # point (identity already is one; arbitrary warm starts are only
        # guaranteed L[v]-in-component, so compress)
        self._labels = fr.compress_full(L0) if init is not None else L0

        self._store_edges = bool(store_edges)
        cap = next_pow2(max(int(min_capacity), 1)) if store_edges else 0
        self._src = jnp.zeros((cap,), jnp.int32)
        self._dst = jnp.zeros((cap,), jnp.int32)
        self._m = 0                      # real (unpadded) edges ingested
        self._n_batches = 0
        # device-resident cumulative counters: no host syncs per batch
        self._iterations = jnp.int32(0)
        self._converged = jnp.array(True)
        self._edges_visited = jnp.float32(0)
        self._snap: Optional[ComponentResult] = None
        self.fault_injector = fault_injector
        # degradation events survived by this stream (kernel fallbacks)
        # plus the resolved execution plan of each distinct per-batch
        # resolution; surfaced through snapshot().provenance
        self._provenance: list = []
        self._last_plan_entry: Optional[str] = None

    # -- introspection ---------------------------------------------------
    @property
    def n_vertices(self) -> int:
        return self._n

    @property
    def n_edges(self) -> int:
        """Real (unpadded) edges ingested so far."""
        return self._m

    @property
    def n_batches(self) -> int:
        return self._n_batches

    @property
    def capacity(self) -> int:
        """Current edge-store capacity (power of two)."""
        return int(self._src.shape[0])

    @property
    def vertex_capacity(self) -> int:
        """Label-array capacity (power of two; growth within it is free)."""
        return self._n_cap

    @property
    def options(self) -> SolveOptions:
        return self._opts

    @property
    def labels(self) -> jax.Array:
        """Device-resident converged labels (min vertex id per component),
        trimmed to the logical vertex count."""
        return self._labels[:self._n]

    def graph(self) -> Graph:
        """The accumulated edge list as a :class:`Graph` (store view)."""
        if not self._store_edges:
            raise ValueError(
                "this engine was built with store_edges=False; the edge "
                "history was not kept")
        return Graph(src=self._src[:self._m], dst=self._dst[:self._m],
                     n_vertices=self._n)

    # -- ingestion -------------------------------------------------------
    def _grow_vertices(self, n: int) -> None:
        if n < self._n:
            raise ValueError(
                f"n_vertices={n} shrinks the stream (was {self._n})")
        if n > self._n:
            # new vertices start as their own singleton components —
            # within capacity they already sit identity-labelled past the
            # logical n, so growth is just a bound bump (no recompile);
            # past capacity the label array doubles (one recompile per
            # doubling, amortised like the edge store)
            if n > self._n_cap:
                new_cap = next_pow2(n)
                self._labels = jnp.concatenate(
                    [self._labels,
                     jnp.arange(self._n_cap, new_cap, dtype=jnp.int32)])
                self._n_cap = new_cap
            self._n = n
            # growth alone changes query results (new singletons), so the
            # cached snapshot is stale even if the batch has no edges
            self._snap = None

    def _ensure_capacity(self, need: int) -> None:
        cap = self.capacity
        if need <= cap:
            return
        new_cap = next_pow2(need)
        grown = jnp.zeros((new_cap,), jnp.int32)
        self._src = grown.at[:cap].set(self._src)
        self._dst = grown.at[:cap].set(self._dst)

    def _validate_batch(self, src: np.ndarray, dst: np.ndarray) -> None:
        # same eager guard as Graph.add_edges: out-of-range ids would be
        # silently clamped by XLA gather/scatter and merge vertex 0's
        # component with the wrong vertices.  Runs on the host-side view
        # *before* device conversion so NumPy input costs no device sync.
        hi = int(max(src.max(), dst.max()))
        lo = int(min(src.min(), dst.min()))
        if hi >= self._n:
            raise ValueError(
                f"edge endpoint {hi} >= n_vertices={self._n}; pass "
                "n_vertices= to grow the stream")
        if lo < 0:
            raise ValueError("edge endpoints must be >= 0")

    def ingest(self, src, dst, n_vertices: Optional[int] = None,
               validate: bool = True) -> "StreamingConnectivity":
        """Ingest one edge micro-batch and re-converge the labels.

        Args:
          src, dst: 1-D arrays of equal length (each undirected edge
            once; duplicates and self-loops are harmless no-ops).
          n_vertices: optionally grow the vertex set first (ids in the
            batch may then use the new range).
          validate: eagerly bounds-check the endpoints (one host sync on
            device input; free for NumPy input).  Disable only for
            pre-validated streams.

        Returns ``self`` (chainable).
        """
        with jax.profiler.TraceAnnotation(tracing.INGEST,
                                          batch=self._n_batches):
            # keep device input on device (no pull unless validating); lift
            # everything else to NumPy so validation is a pure host check
            if not isinstance(src, jax.Array):
                src = np.asarray(src)
            if not isinstance(dst, jax.Array):
                dst = np.asarray(dst)
            if np.shape(src) != np.shape(dst) or len(np.shape(src)) != 1:
                raise ValueError(
                    f"src/dst must be equal-length 1-D, got {np.shape(src)} "
                    f"vs {np.shape(dst)}")
            old_n = self._n
            if n_vertices is not None:
                self._grow_vertices(int(n_vertices))
            k = int(np.shape(src)[0])
            if k == 0:
                return self
            if validate:
                with jax.profiler.TraceAnnotation(tracing.INGEST_VALIDATE):
                    self._validate_batch(np.asarray(src), np.asarray(dst))

            pad_k = next_pow2(k)
            with jax.profiler.TraceAnnotation(tracing.INGEST_PAD):
                src_p, dst_p = _pad_batch(jnp.asarray(src, jnp.int32),
                                          jnp.asarray(dst, jnp.int32), pad_k)

            # delta re-convergence: sweep only the new batch, warm-started.
            # Everything up to the scalar commit below runs inside the
            # rollback guard — vertex growth rolls back on failure (surplus
            # label capacity is invisible identity padding) and ring writes
            # only ever touch slots >= _m, which no reader observes — so a
            # failure anywhere (backend compile error, OOM at a new bucket
            # size, an injected crash after the ring write) leaves the
            # engine exactly as it was: ingest is atomic.
            try:
                if self.fault_injector is not None:
                    self.fault_injector.maybe_fail(self._n_batches, "pre")
                L, it, done, visited = self._delta_solve(src_p, dst_p,
                                                         pad_k, k)
                if self._store_edges:
                    with jax.profiler.TraceAnnotation(tracing.INGEST_STORE):
                        self._ensure_capacity(self._m + pad_k)
                        offset = jnp.int32(self._m)
                        self._src = _ring_write(self._src, src_p, offset)
                        self._dst = _ring_write(self._dst, dst_p, offset)
                if self.fault_injector is not None:
                    self.fault_injector.maybe_fail(self._n_batches,
                                                   "post_write")
            except Exception:
                self._n = old_n
                self._snap = None
                raise
            # commit: the ring store already holds the batch (padding slots
            # hold self-loops; the next batch's write cursor starts at the
            # real size and overwrites them) — publish the size, labels and
            # counters in one uninterruptible run of scalar rebinds
            with jax.profiler.TraceAnnotation(tracing.INGEST_COMMIT):
                self._m += k
                self._labels = L
                self._iterations = (self._iterations
                                    + jnp.asarray(it, jnp.int32))
                self._converged = self._converged & jnp.asarray(done, bool)
                self._edges_visited = (self._edges_visited
                                       + jnp.asarray(visited, jnp.float32))
                self._n_batches += 1
                self._snap = None
            return self

    def _delta_solve(self, src_p, dst_p, pad_k: int, k: int):
        """Run the per-batch delta solve, falling back to XLA on a failed
        non-XLA kernel launch (recorded in the stream's provenance)."""
        try:
            return self._delta_solve_backend(src_p, dst_p, pad_k, k,
                                             self._opts)
        except Exception as exc:
            if (not self._opts.kernel_fallback
                    or self._opts.backend == "xla"
                    or not is_transient_error(exc)):
                raise
            try:
                # TTL'd demotion: later batches (and later streams) in
                # this size bucket resolve straight to XLA until it lapses
                _planner.record_kernel_failure(
                    self._n_cap, sweep_edges(pad_k, self._opts),
                    failed_backend=self._opts.backend)
            except Exception:
                pass  # cache writes must never break the fallback
            self._provenance.append(
                f"kernel_fallback:{self._opts.backend}->xla "
                f"(batch {self._n_batches}, {type(exc).__name__}: "
                f"{str(exc)[:120]})")
            out = self._delta_solve_backend(
                src_p, dst_p, pad_k, k,
                self._opts.replace(backend="xla", plan=None))
            self._snap = None
            return out

    def _record_plan(self, plan) -> None:
        """Append the resolved plan to provenance when it changes."""
        entry = plan.provenance_entry()
        if entry != self._last_plan_entry:
            self._provenance.append(entry)
            self._last_plan_entry = entry
            self._snap = None

    def _delta_solve_backend(self, src_p, dst_p, pad_k: int, k: int, opts):
        with jax.profiler.TraceAnnotation(tracing.INGEST_PLAN):
            backend, plan = resolve_backend_plan(self._n_cap, pad_k, opts)
            self._record_plan(plan)
        with jax.profiler.TraceAnnotation(tracing.INGEST_DELTA_SOLVE):
            if opts.mesh is not None:
                # supervertex rewrite (the single-device path does this
                # inside delta_converge); self-loop padding maps to
                # self-loops.  The replica spans the label *capacity* so
                # its shape matches the resident labels.
                return dist.distributed_contour(
                    Graph(src=self._labels[src_p], dst=self._labels[dst_p],
                          n_vertices=self._n_cap),
                    opts.mesh,
                    edge_axes=tuple(opts.edge_axes),
                    local_rounds=opts.local_rounds,
                    max_iters=opts.max_iters,
                    async_compress=opts.async_compress,
                    backend=backend,
                    plan=plan,
                    init_labels=self._labels,
                    sampling=opts.sampling,
                    compact_every=opts.compact_every,
                    n_active=k)
            return delta_converge(
                src_p, dst_p, self._labels, jnp.int32(k),
                variant=opts.variant,
                backend=backend,
                plan=plan,
                warmup=opts.warmup,
                async_compress=opts.async_compress,
                sampling=opts.sampling,
                compact_every=opts.compact_every,
                max_iters=opts.max_iters)

    def ingest_graph(self, graph: Graph,
                     validate: bool = True) -> "StreamingConnectivity":
        """Ingest a whole :class:`Graph` as one batch (growing vertices)."""
        return self.ingest(graph.src, graph.dst,
                           n_vertices=max(self._n, graph.n_vertices),
                           validate=validate)

    # -- queries (no re-solve) -------------------------------------------
    def snapshot(self) -> ComponentResult:
        """Current components as a :class:`ComponentResult` — O(1).

        Labels are already converged (every ``ingest`` re-converges), so
        this wraps the resident arrays; ``iterations``/``edges_visited``
        are cumulative over the stream and ``converged`` is the AND of
        every batch's fixed-point flag (False means some batch exhausted
        ``max_iters`` — call :meth:`resolve` to repair).
        """
        if self._snap is None:
            self._snap = make_result(self._labels[:self._n],
                                     self._iterations, self._converged,
                                     self._edges_visited,
                                     provenance=self._provenance)
        return self._snap

    def _check_query_ids(self, *ids) -> None:
        # eager host-side bounds check: a jax-array gather against the
        # resident labels would *clamp* an out-of-range id to a valid
        # index and silently answer for the wrong vertex (the PR-3
        # negative-warm-start failure class); the serving coalescer
        # performs the same check before its batched device gather
        for x in ids:
            a = np.asarray(x)
            if np.any(a < 0):
                raise IndexError("vertex ids must be >= 0")
            if a.size and np.any(a >= self._n):
                raise IndexError(
                    f"query vertex id out of range for "
                    f"n_vertices={self._n}; grow the stream with "
                    "ingest(..., n_vertices=...) first")

    def same_component(self, u, v):
        """True iff ``u`` and ``v`` are currently connected."""
        self._check_query_ids(u, v)
        return self.snapshot().same_component(u, v)

    def component_of(self, v):
        """Current component id (min vertex id) of ``v``."""
        self._check_query_ids(v)
        return self.snapshot().component_of(v)

    @property
    def n_components(self) -> int:
        return self.snapshot().n_components

    # -- repair ----------------------------------------------------------
    def resolve(self, max_iters: Optional[int] = None) -> ComponentResult:
        """Full warm-started solve over every stored edge.

        Normally a (cheap) no-op — the delta path keeps labels at the
        fixed point, and the warm start means the resweep converges in
        O(1) iterations.  It is the repair path when ``snapshot().
        converged`` is False (a batch ran out of ``max_iters`` mid-merge,
        leaving store edges that were never fully swept).  The repair
        deliberately does *not* inherit the stream's ``max_iters`` — that
        budget's exhaustion is what it exists to fix; ``None`` takes the
        solver's registry default (pass a value to cap it).
        """
        if self._m == 0:
            return self.snapshot()
        res = solve(self.graph(),
                    self._opts.replace(warm_start=None,
                                       max_iters=max_iters),
                    warm_start=self._labels[:self._n])
        # restore the capacity invariant: identity labels past logical n
        self._labels = jnp.concatenate(
            [jnp.asarray(res.labels, jnp.int32),
             jnp.arange(self._n, self._n_cap, dtype=jnp.int32)])
        self._iterations = self._iterations + res.iterations
        self._converged = jnp.asarray(res.converged, bool)
        if res.edges_visited is not None:
            self._edges_visited = self._edges_visited + res.edges_visited
        self._snap = None
        return self.snapshot()

    # -- checkpointing (DESIGN.md §12) -----------------------------------
    def state_dict(self) -> dict:
        """The engine's complete checkpointable state, as a flat pytree.

        Everything a restore needs to resume the stream bit-exactly: the
        ring-buffered edge store, the converged label array (at capacity,
        so the pow2 growth schedule replays identically), the logical
        sizes, and the cumulative counters.  Every leaf is an array (or
        NumPy scalar), so the dict round-trips through
        ``CheckpointManager``'s atomic-rename ``.npy`` protocol
        unchanged.

        The edge-store leaves are *copies*: the live buffers are donated
        to ``_ring_write`` on the next ingest, which would invalidate any
        held reference — a snapshot must stay readable after the stream
        moves on.
        """
        return {
            "labels": self._labels,
            "src": jnp.array(self._src),
            "dst": jnp.array(self._dst),
            "m": np.int64(self._m),
            "n": np.int64(self._n),
            "n_cap": np.int64(self._n_cap),
            "n_batches": np.int64(self._n_batches),
            "iterations": self._iterations,
            "converged": self._converged,
            "edges_visited": self._edges_visited,
            "store_edges": np.bool_(self._store_edges),
        }

    @classmethod
    def _state_like(cls) -> dict:
        """Structure template for ``CheckpointManager.restore`` (only the
        treedef is used; shapes/dtypes come from the manifest)."""
        return {k: np.int64(0) for k in cls._STATE_KEYS}

    def load_state_dict(self, state: dict) -> "StreamingConnectivity":
        """Restore the engine to a :meth:`state_dict` snapshot in place.

        Validates the structural invariants (capacity/size consistency)
        so a corrupt or truncated checkpoint fails loudly instead of
        answering queries from inconsistent state.
        """
        missing = set(self._STATE_KEYS) - set(state)
        if missing:
            raise ValueError(f"checkpoint state is missing {sorted(missing)}")
        n = int(state["n"])
        n_cap = int(state["n_cap"])
        m = int(state["m"])
        labels = jnp.asarray(state["labels"], jnp.int32)
        # copy the edge store (jnp.array, not asarray): the engine will
        # donate these buffers to _ring_write, which must not invalidate
        # the caller's state dict
        src = jnp.array(state["src"]).astype(jnp.int32)
        dst = jnp.array(state["dst"]).astype(jnp.int32)
        if labels.shape != (n_cap,) or not 0 <= n <= n_cap:
            raise ValueError(
                f"corrupt checkpoint: labels shape {labels.shape} vs "
                f"n={n}, n_cap={n_cap}")
        if src.shape != dst.shape or (bool(state["store_edges"])
                                      and m > src.shape[0]):
            raise ValueError(
                f"corrupt checkpoint: edge store {src.shape}/{dst.shape} "
                f"cannot hold m={m}")
        self._n, self._n_cap, self._m = n, n_cap, m
        self._labels = labels
        self._src, self._dst = src, dst
        self._store_edges = bool(state["store_edges"])
        self._n_batches = int(state["n_batches"])
        self._iterations = jnp.asarray(state["iterations"], jnp.int32)
        self._converged = jnp.asarray(state["converged"], bool)
        self._edges_visited = jnp.asarray(state["edges_visited"],
                                          jnp.float32)
        self._snap = None
        return self

    def save(self, manager, step: Optional[int] = None) -> int:
        """Checkpoint the stream through ``manager`` (atomic rename).

        ``step`` defaults to :attr:`n_batches` — the number of committed
        batches — so the crash-restart driver's convention "checkpoint
        step k == resume at batch k" holds without bookkeeping.  Returns
        the step written.
        """
        if step is None:
            step = self._n_batches
        manager.save(int(step), self.state_dict())
        return int(step)

    @classmethod
    def restore(
        cls,
        manager,
        options: Optional[SolveOptions] = None,
        *,
        step: Optional[int] = None,
        fault_injector: Optional[FaultInjector] = None,
        **overrides,
    ) -> tuple["StreamingConnectivity", int]:
        """Rebuild an engine from a checkpoint written by :meth:`save`.

        ``options`` (plus ``**overrides``) are *not* checkpointed —
        solver configuration may legitimately change across a restart
        (e.g. an elastic mesh over fewer devices) — so pass the same
        options to resume identically.  Returns ``(engine, step)``.
        """
        state, step = manager.restore(cls._state_like(), step)
        eng = cls(int(state["n"]), options,
                  store_edges=bool(state["store_edges"]),
                  fault_injector=fault_injector, **overrides)
        eng.load_state_dict(state)
        return eng, int(step)
