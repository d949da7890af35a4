"""Names of the program's trace scopes and spans: the one list of them.

Record a solve or a stream with JAX's profiler::

    import jax, repro

    with jax.profiler.trace("/tmp/repro-trace"):
        repro.solve(graph).labels.block_until_ready()
        stream.ingest(src, dst)
        stream.labels.block_until_ready()

and open ``/tmp/repro-trace/plugins/profile/<time>/`` in TensorBoard or
Perfetto.  Two kinds of name land in it, on one clock.

**Device scopes** (``jax.named_scope``).  They are trace-time metadata
and cost nothing at run time: each device operation's name path (the
``tf_op`` of its trace event, the ``op_name`` of its HLO metadata)
carries the scopes it was traced under, below its jitted function and
loop, e.g. ``jit(contour_labels)/while/body/mm_sweep/binning/gather``.
Every caller of the scoped function inherits the scope: ``solve()``,
``delta_converge``, the sharded path and ``contour_cc_fixpoint``.

* ``mm_sweep``: one MM^h sweep, on any backend.  Under it:
  ``update_stream`` (the 2h*m (target, value) gathers of the binned
  sweep), ``binning`` (radix binning of the update stream: argsort,
  permutation gathers, bin counts, padded scatters, chunk map),
  ``scatter_kernel`` (the Mosaic kernel ``contour_scatter_min``),
  ``fused_relax`` (the fused single-tile relabel + scatter-min pass) and
  ``scatter_min`` (the XLA scatter-min sweep).
* ``pointer_jump``: label compression ``L <- min(L, L[L])``.
* ``converge_check``: the early-convergence test (paper §III-B2).
* ``compact``: frontier contraction of the active edges.
* ``compress``: the post-loop compression to a star forest.
* ``sampling_prep``: the sampling strategy's edge permutation.
* ``supervertex_rewrite``: a stream batch's endpoints rewritten to roots.
* ``pad``, ``store``: a stream batch padded to its bucket, and written
  into the edge store.
* ``label_allreduce``: the sharded solve's label ``pmin``.

**Host spans** (``jax.profiler.TraceAnnotation``).  They land on the
profiler's host plane; a span costs under a microsecond while nothing
is traced.  ``repro.ingest`` carries the batch index (``batch``) and
``repro.solve`` a per-process call count (``call``), as arguments after
``#`` in the span's recorded name.

* ``repro.solve``: one ``solve()`` call.  Under it ``repro.solve.plan``
  (plan resolution, the tuning-cache read included),
  ``repro.solve.dispatch`` (the solver call: tracing or dispatch of its
  device program) and ``repro.solve.result`` (the result's
  construction).
* ``repro.ingest``: one ``StreamingConnectivity.ingest()``.  Under it
  ``repro.ingest.validate`` (the batch's host bounds check, which pulls a
  device batch to the host), ``repro.ingest.pad`` (dispatch of the
  padding program), ``repro.ingest.plan`` (plan resolution, the
  tuning-cache read included), ``repro.ingest.delta_solve`` (dispatch of
  the delta solve), ``repro.ingest.store`` (edge-store growth and both
  ring writes) and ``repro.ingest.commit`` (the counters' rebinds).

Program span names begin with ``repro.``, so that none is taken for a
span of a caller's own (a benchmark's ``solve`` or ``ingest``).
"""

# -- device scopes (jax.named_scope) --------------------------------------
MM_SWEEP = "mm_sweep"
UPDATE_STREAM = "update_stream"
BINNING = "binning"
SCATTER_KERNEL = "scatter_kernel"
FUSED_RELAX = "fused_relax"
SCATTER_MIN = "scatter_min"
POINTER_JUMP = "pointer_jump"
CONVERGE_CHECK = "converge_check"
COMPACT = "compact"
COMPRESS = "compress"
SUPERVERTEX_REWRITE = "supervertex_rewrite"
SAMPLING_PREP = "sampling_prep"
PAD = "pad"
STORE = "store"
LABEL_ALLREDUCE = "label_allreduce"

DEVICE_SCOPES = (MM_SWEEP, UPDATE_STREAM, BINNING, SCATTER_KERNEL,
                 FUSED_RELAX, SCATTER_MIN, POINTER_JUMP, CONVERGE_CHECK,
                 COMPACT, COMPRESS, SUPERVERTEX_REWRITE, SAMPLING_PREP, PAD,
                 STORE, LABEL_ALLREDUCE)

# the blocked sweep's Mosaic kernel (the pallas_call's name)
SCATTER_KERNEL_NAME = "contour_scatter_min"

# -- host spans (jax.profiler.TraceAnnotation) ----------------------------
SOLVE = "repro.solve"
SOLVE_PLAN = "repro.solve.plan"
SOLVE_DISPATCH = "repro.solve.dispatch"
SOLVE_RESULT = "repro.solve.result"

INGEST = "repro.ingest"
INGEST_VALIDATE = "repro.ingest.validate"
INGEST_PAD = "repro.ingest.pad"
INGEST_PLAN = "repro.ingest.plan"
INGEST_DELTA_SOLVE = "repro.ingest.delta_solve"
INGEST_STORE = "repro.ingest.store"
INGEST_COMMIT = "repro.ingest.commit"

HOST_SPANS = (SOLVE, SOLVE_PLAN, SOLVE_DISPATCH, SOLVE_RESULT, INGEST,
              INGEST_VALIDATE, INGEST_PAD, INGEST_PLAN, INGEST_DELTA_SOLVE,
              INGEST_STORE, INGEST_COMMIT)
