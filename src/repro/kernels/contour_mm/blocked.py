"""Label-blocked, vectorized scatter-min Pallas kernel (DESIGN.md §3.4).

The seed kernel (`kernel.py`) keeps the whole label array ``L`` resident in
VMEM and relaxes edges one at a time on the scalar unit — a hard ceiling of
n ≈ 3M vertices and zero VPU utilisation.  This module lifts both limits
with the two-phase *label-blocked* scheme:

Phase 1 — radix binning (device-side XLA, inside the same jit):
  The MM^h sweep is first reduced to an *update stream*: ``2h·m`` pairs
  ``(target, value)`` where ``value = z = min(L^h[w], L^h[v])`` and the
  targets are the conditional-assignment positions ``{w, v, L[w], …}``
  (`ops.mm_update_stream`).  The stream is stably sorted by
  ``target // label_block`` — the radix bin — and each bin's segment is
  padded up to a multiple of ``chunk_updates`` so that **no chunk straddles
  a label-block boundary**.  A chunk→block map is derived with a
  ``searchsorted`` over the padded bin offsets.

Phase 2 — one ``pallas_call`` over update chunks:
  The grid walks the padded stream chunk by chunk; the chunk→block map is
  a *scalar-prefetch* operand, so the BlockSpec index map for ``L`` can
  place exactly the right ``label_block``-sized tile of ``L`` in VMEM for
  each grid step (``lambda c, m: (m[c], 0, 0)``).  Chunks of the same bin
  are contiguous, so each tile is loaded/flushed once per sweep and
  revisited in place across its chunks.  Each chunk's targets and values
  are DMA'd into SMEM; the kernel walks them on the scalar unit and folds
  each update into the tile with one vector compare + select + min — the
  tile lives in vregs as an ``(8k, 128)`` int32 block, so no layout cast
  and no atomics are needed.

Mosaic layout rules shape the tiles: ``L`` is reshaped to
``(n_blocks, rows, 128)`` so every tile is a dense 2-D block, and on TPU
the chunk length must be a multiple of 1024 (the tiling XLA gives a 1-D
int32 array in HBM).  The chunk→block map lives in the 1 MiB SMEM, so the
chunk length doubles until the map fits :data:`SMEM_MAP_MAX_CHUNKS`.
VMEM per grid step is one tile — independent of ``n``, so there is no
vertex ceiling.  The per-sweep result is bit-exact equal to the
synchronous ``lab.mm_relax`` scatter-min (both compute
``L.at[targets].min(values)``), hence identical fixed point.

Index arithmetic uses int32 positions into the update stream; callers keep
``2h·m + n_blocks·chunk_updates < 2^31`` (enforced below).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import tracing

# Padding slots carry this value; min() makes them no-ops.
_SENTINEL = jnp.iinfo(jnp.int32).max

_LANES = 128
# XLA tiles a 1-D int32 array in TPU HBM in runs of 1024 elements; a 1-D
# block that Mosaic DMAs must be a multiple of it.
HBM_1D_TILE = 1024
# Entries of the scalar-prefetched chunk->block map (int32).  SMEM is
# 1 MiB on v5e; half of it for the map leaves room for the chunk blocks.
SMEM_MAP_MAX_CHUNKS = 1 << 17
# Longest chunk grown to fit the map: its two double-buffered SMEM blocks
# then take 128 KiB.
MAX_CHUNK_UPDATES = 8192
# Updates folded per trip of the in-kernel scalar loop (Mosaic unrolls
# only fully or not at all, so the body is unrolled by hand).
_UNROLL = 8


def _round_up(x, k):
    return (x + k - 1) // k * k


def resolve_interpret(interpret: Optional[bool]) -> bool:
    """``interpret`` as given, else interpreter mode iff no TPU backs JAX."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return bool(interpret)


def _out_struct(shape, dtype, *operands) -> jax.ShapeDtypeStruct:
    """Kernel output type; inside ``shard_map`` it varies over the union of
    the operands' manual axes (``check_vma`` requires it to be stated)."""
    vma = frozenset().union(*(jax.typeof(x).vma for x in operands))
    return jax.ShapeDtypeStruct(shape, dtype, vma=vma)


def _scatter_chunk(n_updates: int, n_bins: int, chunk_updates: int) -> int:
    """The chunk length the binned kernel runs at.

    ``chunk_updates`` doubled until the chunk->block map
    (``ceil(K / chunk) + n_bins`` entries) fits SMEM.
    """
    E = int(chunk_updates)
    while -(-n_updates // E) + n_bins > SMEM_MAP_MAX_CHUNKS:
        if E >= MAX_CHUNK_UPDATES:
            raise ValueError(
                f"{n_updates} updates in {n_bins} label blocks overflow "
                f"the SMEM chunk map ({SMEM_MAP_MAX_CHUNKS} entries); "
                "raise label_block or split the sweep")
        E *= 2
    return E


def _scatter_min_kernel(label_block: int, chunk: int):
    """Build the per-chunk kernel body for the given static tile sizes."""
    unroll = _UNROLL if chunk % _UNROLL == 0 else 1

    def kernel(map_ref, live_ref, t_ref, v_ref, l_in_ref, l_ref):
        c = pl.program_id(0)
        b = map_ref[c]
        # Output VMEM windows are uninitialized on each tile's first grid
        # visit — the HBM-level input/output aliasing does not seed them —
        # so start the accumulator from the fetched input tile.  Chunks of
        # a bin are contiguous, so "first visit" is a map transition.
        prev_b = map_ref[jnp.maximum(c - 1, 0)]

        @pl.when((c == 0) | (b != prev_b))
        def _():
            l_ref[...] = l_in_ref[...]

        # Frontier skip: chunks past the live count hold only updates from
        # inactive edges (binned past the last real label block), so the
        # whole combine is elided — the work-adaptive contraction schedule
        # shrinks per-sweep compute, not just the counted edge visits.
        @pl.when(c < live_ref[0])
        def _():
            shape = l_ref.shape
            slot = (jax.lax.broadcasted_iota(jnp.int32, shape, 0) * _LANES
                    + jax.lax.broadcasted_iota(jnp.int32, shape, 1))
            base = b * label_block

            # Vectorized scatter-min: each update (scalar target, value
            # from SMEM) is compared against every slot of the tile and
            # min-folded where it hits.  One accumulator per unrolled
            # update keeps the min chains independent.  Padding (value
            # _SENTINEL) and slots past label_block never lower anything.
            # The tile joins the accumulators only after the loop: inside
            # shard_map it is device-varying and the loop carry is not.
            def body(j, accs):
                out = []
                for u, acc in enumerate(accs):
                    i = j * unroll + u
                    hit = slot == t_ref[i] - base
                    out.append(jnp.minimum(
                        acc, jnp.where(hit, v_ref[i], _SENTINEL)))
                return tuple(out)

            init = (jnp.full(shape, _SENTINEL, jnp.int32),) * unroll
            acc = l_ref[...]
            for a in jax.lax.fori_loop(0, chunk // unroll, body, init):
                acc = jnp.minimum(acc, a)
            l_ref[...] = acc

    return kernel


def binned_scatter_min_pallas(
    L: jax.Array,
    targets: jax.Array,
    values: jax.Array,
    *,
    label_block: int = 1024,
    chunk_updates: int = 1024,
    interpret: Optional[bool] = None,
    valid: jax.Array = None,
) -> jax.Array:
    """``L.at[targets].min(values)`` with ``L`` tiled by label block.

    Args:
      L: int32[n] labels.
      targets: int32[K] update positions, each in ``[0, n)``.
      values: int32[K] update values (``< _SENTINEL``).
      label_block: tile height ``B``; the VMEM tile is
        ``(ceil(B / 128), 128)`` int32, dense when ``B % 1024 == 0``.
      chunk_updates: updates processed per grid step (a floor: see
        :func:`_scatter_chunk`); a multiple of 1024 on TPU.
      interpret: Pallas interpreter mode; default: True off-TPU.
      valid: optional bool[K] per-update liveness (the work-adaptive
        frontier mask).  Dead updates are radix-binned into a trailing
        *dead bin* past every label block; because bins are contiguous the
        dead updates occupy the tail chunks of the padded stream, and the
        kernel elides the combine for every chunk past the live count
        (scalar-prefetched), skipping whole grid steps of VPU work.
    """
    interpret = resolve_interpret(interpret)
    n = L.shape[0]
    K = targets.shape[0]
    B = int(label_block)
    n_blocks = (n + B - 1) // B
    # With a frontier mask, dead updates get a bin of their own past the
    # last real block so the stable radix sort pushes them to the tail.
    n_bins = n_blocks + (0 if valid is None else 1)
    if not interpret and chunk_updates % HBM_1D_TILE:
        raise ValueError(
            f"chunk_updates={chunk_updates} must be a multiple of "
            f"{HBM_1D_TILE} on TPU (XLA's tiling of 1-D int32 arrays)")
    E = _scatter_chunk(K, n_bins, chunk_updates)
    if K + n_bins * E >= 2**31:
        raise ValueError(
            f"update stream of {K} + {n_bins}*{E} padding overflows int32 "
            "positions; raise label_block or split the sweep")
    rows = -(-B // _LANES)
    # tile b holds L[b*B : (b+1)*B] in its first B slots (row-major)
    L_tiles = jnp.pad(L, (0, n_blocks * B - n),
                      constant_values=_SENTINEL).reshape(n_blocks, B)
    L_tiles = jnp.pad(L_tiles, ((0, 0), (0, rows * _LANES - B)),
                      constant_values=_SENTINEL).reshape(
                          n_blocks, rows, _LANES)

    # -- Phase 1: radix-bin the update stream by target // B ---------------
    with jax.named_scope(tracing.BINNING):
        blk = targets // B
        if valid is not None:
            # dead updates: banished to the tail bin AND value-neutralised,
            # so the grid-step skip is an optimisation, not a correctness
            # gate
            blk = jnp.where(valid, blk, n_blocks)
            values = jnp.where(valid, values, _SENTINEL)
        order = jnp.argsort(blk, stable=True)
        t_sorted = targets[order]
        v_sorted = values[order]
        blk_sorted = blk[order]

        counts = jnp.bincount(blk, length=n_bins)
        padded_counts = _round_up(counts, E)
        offsets = jnp.concatenate(
            [jnp.zeros((1,), counts.dtype), jnp.cumsum(padded_counts)[:-1]])
        seg_start = jnp.concatenate(
            [jnp.zeros((1,), counts.dtype), jnp.cumsum(counts)[:-1]])
        # position in the boundary-aligned padded layout
        pos = offsets[blk_sorted] + (jnp.arange(K) - seg_start[blk_sorted])

        T = _round_up(K, E) + n_bins * E  # static capacity >= sum(padded)
        t_pad = jnp.zeros((T,), targets.dtype).at[pos].set(t_sorted)
        v_pad = jnp.full((T,), _SENTINEL, values.dtype).at[pos].set(v_sorted)

        n_chunks = T // E
        chunk_block = jnp.clip(
            jnp.searchsorted(offsets, jnp.arange(n_chunks) * E,
                             side="right") - 1,
            0, n_blocks - 1).astype(jnp.int32)
        # Chunks holding live updates end where the dead bin begins;
        # without a mask every chunk is live.  (Dead entries were
        # value-masked to _SENTINEL above, so even a combine that did run
        # would be a no-op — the skip saves compute, it is not
        # load-bearing for correctness.)
        if valid is None:
            live_chunks = jnp.full((1,), n_chunks, jnp.int32)
        else:
            dead_start = jnp.cumsum(padded_counts)[n_blocks - 1]
            live_chunks = (dead_start // E).astype(jnp.int32).reshape((1,))

    # -- Phase 2: one pallas_call over chunks, L tiled by BlockSpec --------
    smem = pltpu.MemorySpace.SMEM
    tile = pl.BlockSpec((None, rows, _LANES), lambda c, m, nl: (m[c], 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n_chunks,),
        in_specs=[
            pl.BlockSpec((E,), lambda c, m, nl: (c,), memory_space=smem),
            pl.BlockSpec((E,), lambda c, m, nl: (c,), memory_space=smem),
            tile,
        ],
        out_specs=tile,
    )
    with jax.named_scope(tracing.SCATTER_KERNEL):
        out = pl.pallas_call(
            _scatter_min_kernel(B, E),
            grid_spec=grid_spec,
            out_shape=_out_struct(L_tiles.shape, L.dtype, t_pad, v_pad,
                                  L_tiles),
            input_output_aliases={4: 0},  # L tile accumulates across chunks
            interpret=interpret,
            name=tracing.SCATTER_KERNEL_NAME,
        )(chunk_block, live_chunks, t_pad, v_pad, L_tiles)
    return out.reshape(n_blocks, rows * _LANES)[:, :B].reshape(-1)[:n]


def _fused_relax_kernel(n_pad: int, chunk: int):
    """Per-edge-chunk body of the fused relabel + scatter-min pass."""

    def kernel(live_ref, s_ref, d_ref, l_ref, out_ref):
        c = pl.program_id(0)

        # single tile, constant index map: the output window persists
        # across every grid step, so one seed suffices
        @pl.when(c == 0)
        def _():
            def seed(i, carry):
                out_ref[i] = l_ref[i]
                return carry

            jax.lax.fori_loop(0, n_pad, seed, 0)

        # frontier skip: chunks wholly past the edge limit are elided
        @pl.when(c < live_ref[0])
        def _():
            def body(i, carry):
                s = s_ref[i]
                d = d_ref[i]
                # relabel: gathers read the unchanged input labels, so the
                # sweep is synchronous
                ls = l_ref[s]
                ld = l_ref[d]
                z = jnp.minimum(l_ref[ls], l_ref[ld])  # min(L²[s], L²[d])
                # Definition-3 targets {src, dst, L[src], L[dst]} take z
                for t in (s, d, ls, ld):
                    out_ref[t] = jnp.minimum(out_ref[t], z)
                return carry

            jax.lax.fori_loop(0, chunk, body, 0)

    return kernel


def fused_relax_pallas(
    L: jax.Array,
    src: jax.Array,
    dst: jax.Array,
    *,
    chunk_edges: int = 1024,
    interpret: Optional[bool] = None,
    edge_limit: jax.Array = None,
) -> jax.Array:
    """One fused order-2 MM sweep: relabel gathers + scatter-min, one pass.

    The binned pipeline materialises the ``4m`` update stream in HBM
    (XLA gathers), radix-sorts it, and only then runs the scatter kernel.
    In the single-tile regime (all of ``L`` fits SMEM) none of that is
    necessary: this kernel walks the *edge list* directly on the scalar
    unit, performs the chain gathers ``L[src], L[dst], L²[src], L²[dst]``
    from an SMEM copy of the input labels, and folds all four conditional
    assignments of Definition 3 into an SMEM accumulator — no stream, no
    sort, no inter-pass HBM traffic.  Every gather reads the unchanged
    input labels, so the sweep is synchronous and bit-exact equal to
    ``lab.mm_relax(L, src, dst, 2)``.

    Args:
      L: int32[n] labels; the ops-layer router keeps ``n`` within one tile
        (``n_pad <= label_block``), 16 KiB of SMEM at n = 4096.
      src, dst: int32[m] edge endpoints in ``[0, n)``.
      chunk_edges: edges per grid step; a multiple of 1024 on TPU.
      interpret: Pallas interpreter mode; default: True off-TPU.
      edge_limit: optional traced int32 frontier bound — edges past it are
        masked to ``(0, 0)`` self-loops (min-mapping no-ops, the
        structs.Graph padding trick) and chunks wholly past it skip their
        grid step outright.
    """
    interpret = resolve_interpret(interpret)
    n = L.shape[0]
    m = src.shape[0]
    E = int(chunk_edges)
    if not interpret and E % HBM_1D_TILE:
        raise ValueError(
            f"chunk_edges={E} must be a multiple of {HBM_1D_TILE} on TPU "
            "(XLA's tiling of 1-D int32 arrays)")
    with jax.named_scope(tracing.FUSED_RELAX):
        n_pad = _round_up(max(n, 1), HBM_1D_TILE)
        L_pad = jnp.pad(L, (0, n_pad - n), constant_values=_SENTINEL)

        if edge_limit is not None:
            mask = jnp.arange(m, dtype=jnp.int32) < edge_limit
            src = jnp.where(mask, src, 0)
            dst = jnp.where(mask, dst, 0)
        T = max(E, _round_up(m, E))
        # (0, 0) self-loop padding: relabels to L[0] and scatters z = L²[0]
        # onto vertex 0 — a no-op under the L[v] <= v labelling invariant
        src_p = jnp.zeros((T,), src.dtype).at[:m].set(src)
        dst_p = jnp.zeros((T,), dst.dtype).at[:m].set(dst)
        n_chunks = T // E
        if edge_limit is None:
            live = jnp.full((1,), n_chunks, jnp.int32)
        else:
            lim = jnp.minimum(jnp.asarray(edge_limit, jnp.int32), m)
            live = ((lim + E - 1) // E).reshape((1,))

        smem = pltpu.MemorySpace.SMEM
        whole = pl.BlockSpec((n_pad,), lambda c, lv: (0,), memory_space=smem)
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(n_chunks,),
            in_specs=[
                pl.BlockSpec((E,), lambda c, lv: (c,), memory_space=smem),
                pl.BlockSpec((E,), lambda c, lv: (c,), memory_space=smem),
                whole,
            ],
            out_specs=whole,
        )
        out = pl.pallas_call(
            _fused_relax_kernel(n_pad, E),
            grid_spec=grid_spec,
            out_shape=_out_struct((n_pad,), L.dtype, src_p, dst_p, L_pad),
            interpret=interpret,
        )(live, src_p, dst_p, L_pad)
        return out[:n]
