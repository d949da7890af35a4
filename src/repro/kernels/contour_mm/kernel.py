"""Pallas TPU kernel: fused 2-order minimum-mapping edge relaxation.

This is the per-core hot loop of the Contour algorithm (paper Alg. 1 line
6-8 plus the §III-B async-update optimisation).  One ``pallas_call``
processes the whole edge shard: the grid walks edge blocks sequentially
(TPU grid order is sequential per core) while the label array ``L`` stays
resident in VMEM across grid steps via a constant-index output BlockSpec
with input/output aliasing — i.e. labels are updated **in place**, so later
edges observe labels already lowered by earlier edges *within the same
sweep*.  That is precisely the paper's asynchronous-update semantics,
realised deterministically (fixed edge order) instead of racily.

TPU adaptation notes (DESIGN.md §3):
  * the conditional CAS assignment (paper Eq. 4) becomes a scalar
    read-min-write on a VMEM ref — no atomics exist or are needed because
    the per-core loop is sequential on the scalar unit;
  * VMEM budget: ``L`` occupies ``4·n`` bytes and the edge block ``8·BE``
    bytes.  With 16 MiB VMEM this kernel handles shards up to n ≈ 3M
    vertices directly; larger graphs use the label-blocked vectorized
    kernel in ``blocked.py`` (updates radix-binned by ``L``-block, ``L``
    tiled via BlockSpec — DESIGN.md §3.4) or the XLA scatter-min path.
    Backend selection lives in ``ops.plan_contour_kernel``.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.contour_mm.blocked import resolve_interpret


def _mm2_kernel(src_ref, dst_ref, l_in_ref, l_ref):
    """Sequential 2-order MM over one edge block; L aliased in/out."""
    del l_in_ref  # aliased with l_ref; reads/writes go through l_ref
    block_edges = src_ref.shape[0]

    def body(e, carry):
        w = src_ref[e]
        v = dst_ref[e]
        lw = l_ref[w]
        lv = l_ref[v]
        z = jnp.minimum(l_ref[lw], l_ref[lv])  # z² = min(L²[w], L²[v])
        # conditional vector assignment (Definition 2/3): lower the four
        # mapped positions {w, v, L[w], L[v]} to z if greater.
        l_ref[w] = jnp.minimum(l_ref[w], z)
        l_ref[v] = jnp.minimum(l_ref[v], z)
        l_ref[lw] = jnp.minimum(l_ref[lw], z)
        l_ref[lv] = jnp.minimum(l_ref[lv], z)
        return carry

    jax.lax.fori_loop(0, block_edges, body, 0)


def mm2_pallas(src: jax.Array, dst: jax.Array, L: jax.Array,
               *, block_edges: int = 512,
               interpret: Optional[bool] = None) -> jax.Array:
    """One full asynchronous 2-order sweep over all edges; returns new L.

    Args:
      src, dst: int32[m] edge endpoints; m must be a multiple of
        ``block_edges`` (pad with self-loops, which are MM no-ops).
      L: int32[n] current labels.
      interpret: run the kernel body in interpret mode; default: True
        off-TPU.  Compiled for TPU, Mosaic refuses the scalar VMEM stores
        (the ops layer raises before lowering).
    """
    interpret = resolve_interpret(interpret)
    m = src.shape[0]
    if m % block_edges != 0:
        raise ValueError(f"m={m} must be a multiple of block_edges={block_edges}")
    n = L.shape[0]
    grid = (m // block_edges,)
    return pl.pallas_call(
        _mm2_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_edges,), lambda i: (i,)),
            pl.BlockSpec((block_edges,), lambda i: (i,)),
            pl.BlockSpec((n,), lambda i: (0,)),  # whole L, resident in VMEM
        ],
        out_specs=pl.BlockSpec((n,), lambda i: (0,)),
        out_shape=jax.ShapeDtypeStruct((n,), L.dtype),
        input_output_aliases={2: 0},  # L updated in place across grid steps
        interpret=interpret,
    )(src, dst, L)
