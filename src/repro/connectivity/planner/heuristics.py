"""Cold-start heuristic tables: the autotuner's prior.

These are the (slightly extended) tables that used to live in
``kernels.contour_mm.ops.plan_contour_kernel``.  They remain the
deterministic fallback whenever the tuning cache has no (valid) entry for
a bucket, and the reference side of the bench ``autotune_gate`` — the
tuned plan must measure no slower than this prior.
"""
from __future__ import annotations

from typing import Optional

import jax

from repro.connectivity.planner.plan import ExecutionPlan, next_pow2
from repro.connectivity.planner.vmem import vmem_budget_bytes

# Past this many edges the staged frontier schedule is worth its extra
# per-stage compiles on the XLA path: each stage re-enters the while loop
# over a physically sliced (pow2-bucketed) edge array, so sweeps and
# contractions stop paying full-m masked work.  Below it the masked
# single-loop schedule wins (one compile, tiny arrays).
STAGED_MIN_EDGES = 1 << 15

# Single-tile regime: the blocked kernel holds all of L in one tile and
# the fused relabel+scatter-min pass is eligible (no update-stream
# materialisation, no radix binning).
SINGLE_TILE_MAX_N = 4096

# Fewest edges at which the TPU table sweeps on XLA's scatter-min above
# the single-tile regime (measured on a v5e, PERF.md section 6).
XLA_SWEEP_MIN_EDGES = 1 << 20

# TPU tile unit of the blocked and fused kernels: one (8, 128) int32 vreg
# of labels, and XLA's tiling of 1-D int32 arrays in HBM (update chunks).
TPU_TILE = 1024
# Label bins the blocked kernel's SMEM chunk map holds comfortably (half
# of blocked.SMEM_MAP_MAX_CHUNKS; the stream's chunks take the rest).
MAX_LABEL_BINS = 1 << 16

# Out-of-core chunk sizing: per-edge device cost of one resident chunk.
# A chunk holds int64 src/dst (16 B/edge) double-buffered (32 B/edge),
# plus the sweep's relabeled copies and contraction temporaries — call it
# 128 B/edge so the derived chunk plus the O(n) label array stay well
# inside the VMEM-scale working-set budget the planner already owns.
OOCORE_BYTES_PER_EDGE = 128


def _round_up(x: int, k: int) -> int:
    return (x + k - 1) // k * k


def heuristic_plan(
    n_vertices: int,
    n_edges: int,
    platform: Optional[str] = None,
) -> ExecutionPlan:
    """Pick backend + tile sizes + schedule for a graph size, by table.

    Off-TPU the only compilable backend is XLA scatter-min.  On TPU the
    table follows what the chip measured (``experiments/sweep_crossover.py``,
    PERF.md section 6):

    * small graphs (n <= :data:`SINGLE_TILE_MAX_N`) hold all of L in one
      tile, where the fused relabel+scatter-min Pallas pass skips the
      update-stream materialisation entirely (L in SMEM);
    * larger graphs with at least :data:`XLA_SWEEP_MIN_EDGES` edges take
      XLA's scatter-min: whole solves ran 3.6x (Delaunay, 3.1M edges) to
      4.5x (Graph500, 2^24 edges) faster on it, since the radix binning
      the blocked kernel needs costs more than XLA's whole sweep;
    * larger graphs with fewer edges, such as a stream's 2^16-edge delta
      solves, keep the label-blocked kernel: there a batch ran about 4%
      slower on XLA's sweep.

    The blocked kernel's tiles: 1024-slot label tiles — one (8, 128) int32
    vreg, so each update costs one vector compare/select/min — grown only
    when the bin count would overflow the kernel's SMEM chunk map, and
    1024-update chunks (XLA's 1-D int32 HBM tiling), which the kernel
    doubles if the stream's chunk map would overflow SMEM.

    ``compact_schedule`` only matters when the caller also enables the
    work-adaptive frontier (``sampling``/``compact_every``): big edge
    lists get the ``"staged"`` physically-sliced realisation, small ones
    keep the masked in-loop schedule.
    """
    platform = platform or jax.default_backend()
    compact = "staged" if n_edges >= STAGED_MIN_EDGES else "masked"
    if platform != "tpu":
        # Pallas TPU kernels cannot compile here; if a caller forces a
        # pallas backend anyway it runs in interpret (validation) mode.
        return ExecutionPlan(backend="xla", interpret=True,
                             compact_schedule=compact, origin="heuristic")
    if n_vertices <= SINGLE_TILE_MAX_N:
        label_block = _round_up(max(n_vertices, 1), TPU_TILE)
        fuse = True
    else:
        # at most MAX_LABEL_BINS bins keep the chunk map within SMEM
        label_block = max(TPU_TILE,
                          next_pow2(-(-n_vertices // MAX_LABEL_BINS)))
        fuse = False
    block_edges = 512 if n_edges < 1 << 20 else 2048
    return ExecutionPlan(
        backend=("xla" if not fuse and n_edges >= XLA_SWEEP_MIN_EDGES
                 else "pallas_blocked"),
        block_edges=block_edges,
        label_block=label_block,
        chunk_updates=TPU_TILE,
        interpret=False,
        compact_schedule=compact,
        fuse_relabel=fuse,
        origin="heuristic",
    )


def oocore_chunk_bucket(
    n_edges: int,
    platform: Optional[str] = None,
    vmem_limit_bytes: Optional[int] = None,
    requested: int = 0,
) -> int:
    """The pow2 edge-chunk bucket the out-of-core streamer runs at.

    ``requested`` (``SolveOptions.oocore_chunk_edges``) wins when set,
    rounded up to a power of two; otherwise the bucket is derived from
    the platform VMEM budget at :data:`OOCORE_BYTES_PER_EDGE`.  Either
    way the result is clamped to ``[MIN_STAGE_EDGES, next_pow2(m)]`` —
    chunks below the stage floor would thrash compiles, and a chunk
    larger than the whole graph is just the in-core path.
    """
    from repro.connectivity.planner.staged import MIN_STAGE_EDGES
    if requested and requested > 0:
        bucket = next_pow2(requested)
    else:
        budget = vmem_budget_bytes(platform, override=vmem_limit_bytes)
        # round *down* to pow2: never exceed the derived byte budget
        bucket = next_pow2(max(budget // OOCORE_BYTES_PER_EDGE, 1))
        if bucket * OOCORE_BYTES_PER_EDGE > budget:
            bucket //= 2
    ceiling = max(next_pow2(n_edges), MIN_STAGE_EDGES)
    return max(MIN_STAGE_EDGES, min(bucket, ceiling))
