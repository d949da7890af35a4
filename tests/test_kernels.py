"""Per-kernel shape/dtype sweeps: Pallas (interpret=True) vs pure-jnp ref."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.graphs import generators as gen
from repro.graphs.oracle import connected_components_oracle

# ---------------------------------------------------------------------------
# contour_mm
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("block_edges", [64, 256, 512])
@pytest.mark.parametrize("gname,make", [
    ("path", lambda: gen.path(800, seed=1)),
    ("rmat", lambda: gen.rmat(10, seed=2)),
    ("grid", lambda: gen.grid2d(24, 24)),
])
def test_contour_mm_kernel_bitexact(gname, make, block_edges):
    from repro.kernels.contour_mm.ops import _pad_edges, contour_mm_step
    from repro.kernels.contour_mm.ref import mm_block_ref

    g = make()
    L0 = jnp.arange(g.n_vertices, dtype=jnp.int32)
    src_p, dst_p = _pad_edges(g.src, g.dst, block_edges)
    out = contour_mm_step(g.src, g.dst, L0, backend="pallas",
                          block_edges=block_edges)
    ref = mm_block_ref(src_p, dst_p, L0)
    assert (np.asarray(out) == np.asarray(ref)).all()


def test_contour_mm_fixpoint_matches_oracle():
    from repro.kernels.contour_mm.ops import contour_cc_fixpoint

    g = gen.components_mix(
        [gen.path(300, seed=1), gen.star(200, seed=2)], seed=3)
    labels, iters, converged, visited = contour_cc_fixpoint(g,
                                                            backend="pallas")
    assert bool(converged)
    assert float(visited) == float(iters) * g.n_edges
    oracle = connected_components_oracle(*g.to_numpy())
    assert (np.asarray(labels) == oracle).all()
    assert iters < 30


def test_contour_mm_xla_backend_matches_sync_ref():
    from repro.kernels.contour_mm.ops import contour_mm_step
    from repro.kernels.contour_mm.ref import mm_sync_ref

    g = gen.rmat(9, seed=5)
    L0 = jnp.arange(g.n_vertices, dtype=jnp.int32)
    out = contour_mm_step(g.src, g.dst, L0, backend="xla")
    ref = mm_sync_ref(g.src, g.dst, L0)
    assert (np.asarray(out) == np.asarray(ref)).all()


# ---------------------------------------------------------------------------
# contour_mm: label-blocked vectorized backend (DESIGN.md §3.4)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("label_block,chunk", [
    (512, 128),    # 8 label blocks at n=4096
    (1024, 256),   # 4 label blocks
    (300, 64),     # 14 blocks, tile not a divisor of n -> L padding path
])
def test_blocked_sweep_bitexact_vs_mm_relax(label_block, chunk):
    """Per-sweep the blocked kernel must equal the scatter-min oracle
    bit-for-bit on graphs whose n spans >= 4 label blocks — including on
    mid-run (non-trivial) label states."""
    from repro.core import labels as lab
    from repro.kernels.contour_mm.ops import contour_mm_step

    g = gen.rmat(12, seed=7)   # n = 4096
    assert g.n_vertices >= 4 * label_block
    L = jnp.arange(g.n_vertices, dtype=jnp.int32)
    for _ in range(3):         # sweep 0 from identity, then mid-run states
        out = contour_mm_step(g.src, g.dst, L, backend="pallas_blocked",
                              label_block=label_block, chunk_updates=chunk)
        ref = lab.mm_relax(L, g.src, g.dst, order=2)
        assert (np.asarray(out) == np.asarray(ref)).all()
        L = ref


@pytest.mark.parametrize("order", [1, 2, 3])
def test_blocked_backend_is_order_generic(order):
    from repro.core import labels as lab
    from repro.kernels.contour_mm.ops import contour_mm_step

    g = gen.grid2d(40, 40)
    L0 = jnp.arange(g.n_vertices, dtype=jnp.int32)
    out = contour_mm_step(g.src, g.dst, L0, backend="pallas_blocked",
                          order=order, label_block=256, chunk_updates=64)
    ref = lab.mm_relax(L0, g.src, g.dst, order=order)
    assert (np.asarray(out) == np.asarray(ref)).all()


def test_blocked_fixpoint_matches_oracle_multiblock():
    """On-device fixpoint on the blocked kernel, n spanning >= 4 blocks."""
    from repro.kernels.contour_mm.ops import contour_cc_fixpoint

    g = gen.components_mix(
        [gen.path(900, seed=1), gen.star(700, seed=2), gen.rmat(10, seed=3)],
        seed=4)
    assert g.n_vertices >= 4 * 512
    labels, iters, converged, _ = contour_cc_fixpoint(
        g, backend="pallas_blocked", label_block=512, chunk_updates=128)
    assert bool(converged)
    oracle = connected_components_oracle(*g.to_numpy())
    assert (np.asarray(labels) == oracle).all()
    assert 1 <= int(iters) < 30


def test_fixpoint_runs_on_device_without_host_sync():
    """`contour_cc_fixpoint` must be a single on-device `lax.while_loop`:
    it is jitted end-to-end, so any seed-style per-iteration
    `bool(converged_early(...))` readback would fail to trace; the lowered
    HLO must contain the while op carrying the convergence flag."""
    from repro.kernels.contour_mm.ops import contour_cc_fixpoint

    g = gen.rmat(9, seed=11)
    txt = contour_cc_fixpoint.lower(g, backend="xla").as_text()
    assert "while" in txt
    labels, iters, _, _ = contour_cc_fixpoint(g, backend="xla")
    oracle = connected_components_oracle(*g.to_numpy())
    assert (np.asarray(labels) == oracle).all()


def test_fixpoint_backends_agree():
    """Every backend reaches the identical min-vertex-id fixed point."""
    from repro.kernels.contour_mm.ops import contour_cc_fixpoint

    g = gen.components_mix([gen.path(300, seed=1), gen.star(200, seed=2)],
                           seed=3)
    oracle = connected_components_oracle(*g.to_numpy())
    for backend in ("xla", "auto", "pallas", "pallas_blocked"):
        labels, iters, _, _ = contour_cc_fixpoint(
            g, backend=backend, label_block=256, chunk_updates=64)
        assert (np.asarray(labels) == oracle).all(), backend
        assert int(iters) < 30, backend


def test_dispatch_plan():
    """The heuristic tables: XLA off-TPU; blocked with sane tiles on TPU."""
    from repro.connectivity.planner import heuristic_plan

    cpu = heuristic_plan(100_000, 1_000_000, platform="cpu")
    assert cpu.backend == "xla"
    assert cpu.interpret            # forced pallas runs in validation mode

    small = heuristic_plan(2_000, 20_000, platform="tpu")
    assert small.backend == "pallas_blocked"
    assert small.label_block >= 2_000       # single tile, no binning waste
    assert not small.interpret
    assert small.fuse_relabel               # single-tile fused pass applies

    big = heuristic_plan(50_000_000, 800_000_000, platform="tpu")
    assert big.backend == "pallas_blocked"  # no vertex ceiling
    # tiles Mosaic compiles densely: whole (8, 128) int32 vregs of labels,
    # chunks in XLA's 1024-element tiling of 1-D int32 arrays
    assert big.label_block % 1024 == 0 and big.chunk_updates % 1024 == 0
    assert big.label_block * big.chunk_updates * 4 <= 4 * 1024 * 1024
    assert not big.fuse_relabel             # multi-tile: binned pipeline

    auto = heuristic_plan(10_000, 80_000)        # this host: not a TPU
    assert auto.backend in ("xla", "pallas_blocked")


def test_scalar_pallas_vmem_ceiling_enforced():
    """Above the whole-L VMEM ceiling the scalar kernel must refuse with a
    clear error (not an opaque Mosaic allocation failure)."""
    from repro.kernels.contour_mm.ops import (WHOLE_L_VMEM_CEILING,
                                              mm_relax_backend)

    n = WHOLE_L_VMEM_CEILING + 1
    L = jnp.zeros((n,), jnp.int32)
    src = jnp.zeros((4,), jnp.int32)
    dst = jnp.ones((4,), jnp.int32)
    with pytest.raises(ValueError, match="ceiling"):
        mm_relax_backend(L, src, dst, backend="pallas")


def test_scalar_pallas_refused_when_compiled():
    """The scalar kernel does not compile for TPU: a compiled request is
    refused before lowering."""
    from repro.kernels.contour_mm.ops import mm_relax_backend

    L = jnp.arange(8, dtype=jnp.int32)
    src = jnp.zeros((4,), jnp.int32)
    with pytest.raises(ValueError, match="does not compile for TPU"):
        mm_relax_backend(L, src, src + 1, backend="pallas", interpret=False)


def test_kernel_entry_points_follow_platform():
    """Without ``interpret`` the kernels pick interpreter mode off-TPU (and
    compile on a TPU); either way a sweep equals the scatter-min oracle."""
    from repro.core import labels as lab
    from repro.kernels.contour_mm.blocked import (binned_scatter_min_pallas,
                                                  fused_relax_pallas)
    from repro.kernels.contour_mm.kernel import mm2_pallas
    from repro.kernels.contour_mm.ops import _pad_edges
    from repro.kernels.contour_mm.ref import mm_block_ref

    g = gen.rmat(9, seed=4)
    L = jnp.arange(g.n_vertices, dtype=jnp.int32)
    ref = np.asarray(lab.mm_relax(L, g.src, g.dst, order=2))
    t, v = lab.mm_update_stream(L, g.src, g.dst, 2)
    assert (np.asarray(binned_scatter_min_pallas(L, t, v)) == ref).all()
    assert (np.asarray(fused_relax_pallas(L, g.src, g.dst)) == ref).all()
    src_p, dst_p = _pad_edges(g.src, g.dst, 512)
    assert (np.asarray(mm2_pallas(src_p, dst_p, L))
            == np.asarray(mm_block_ref(src_p, dst_p, L))).all()


def test_auto_backend_step_matches_mm_relax():
    from repro.core import labels as lab
    from repro.kernels.contour_mm.ops import contour_mm_step

    g = gen.erdos_renyi(2_000, 5.0, seed=9)
    L0 = jnp.arange(g.n_vertices, dtype=jnp.int32)
    out = contour_mm_step(g.src, g.dst, L0, backend="auto")
    ref = lab.mm_relax(L0, g.src, g.dst, order=2)
    assert (np.asarray(out) == np.asarray(ref)).all()


# ---------------------------------------------------------------------------
# flash_attention
# ---------------------------------------------------------------------------

FLASH_CASES = [
    # (b, h, hkv, t, hd, causal, dtype, blocks)
    (2, 4, 4, 128, 64, True, jnp.float32, (64, 64)),
    (2, 4, 2, 256, 64, True, jnp.float32, (64, 128)),
    (1, 8, 1, 192, 32, True, jnp.float32, (64, 64)),       # MQA
    (1, 8, 2, 130, 32, True, jnp.bfloat16, (64, 64)),      # ragged pad
    (2, 4, 4, 128, 64, False, jnp.float32, (64, 64)),
    (1, 2, 2, 512, 128, True, jnp.bfloat16, (128, 128)),
]


@pytest.mark.slow  # interpret-mode Pallas, 3-6s per case
@pytest.mark.parametrize("b,h,hkv,t,hd,causal,dtype,blocks", FLASH_CASES)
def test_flash_attention_sweep(b, h, hkv, t, hd, causal, dtype, blocks):
    from repro.kernels.flash_attention.ops import flash_attention
    from repro.kernels.flash_attention.ref import mha_ref

    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (b, h, t, hd), dtype)
    k = jax.random.normal(ks[1], (b, hkv, t, hd), dtype)
    v = jax.random.normal(ks[2], (b, hkv, t, hd), dtype)
    out = flash_attention(q, k, v, causal=causal,
                          block_q=blocks[0], block_k=blocks[1])
    ref = mha_ref(q, k, v, causal=causal)
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        atol=tol, rtol=tol)


@pytest.mark.slow  # interpret-mode Pallas
def test_flash_matches_model_attention_path():
    """Kernel vs the model's XLA chunked path (the dry-run lowering)."""
    from repro.kernels.flash_attention.ops import flash_attention
    from repro.models.attention import attend_chunked

    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    b, h, hkv, t, hd = 2, 8, 2, 256, 64
    q = jax.random.normal(ks[0], (b, t, h, hd), jnp.float32)
    k = jax.random.normal(ks[1], (b, t, hkv, hd), jnp.float32)
    v = jax.random.normal(ks[2], (b, t, hkv, hd), jnp.float32)
    xla = attend_chunked(q, k, v, causal=True, q_chunk=64, kv_chunk=64)
    pallas = flash_attention(
        q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3), causal=True, block_q=64, block_k=64
    ).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(xla), np.asarray(pallas),
                               atol=2e-5, rtol=2e-5)


# ---------------------------------------------------------------------------
# fused_rmsnorm
# ---------------------------------------------------------------------------

RMS_CASES = [
    (64, 512, jnp.float32),
    (33, 768, jnp.bfloat16),     # non-divisible rows -> padding path
    (7, 128, jnp.float32),
    (256, 2048, jnp.bfloat16),
    (1, 8192, jnp.float32),      # wide row, shrunken block
]


@pytest.mark.slow  # interpret-mode Pallas
@pytest.mark.parametrize("r,d,dtype", RMS_CASES)
def test_fused_rmsnorm_sweep(r, d, dtype):
    from repro.kernels.fused_rmsnorm.ops import fused_rmsnorm
    from repro.kernels.fused_rmsnorm.ref import rmsnorm_ref

    x = jax.random.normal(jax.random.PRNGKey(2), (r, d), dtype)
    w = jax.random.normal(jax.random.PRNGKey(3), (d,), dtype)
    out = fused_rmsnorm(x, w)
    ref = rmsnorm_ref(x, w)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=1e-6 if dtype == jnp.float32 else 1e-2,
                               rtol=1e-6 if dtype == jnp.float32 else 1e-2)


def test_fused_rmsnorm_batched_shape():
    from repro.kernels.fused_rmsnorm.ops import fused_rmsnorm

    x = jax.random.normal(jax.random.PRNGKey(4), (2, 5, 256), jnp.float32)
    w = jnp.ones((256,), jnp.float32)
    out = fused_rmsnorm(x, w)
    assert out.shape == x.shape
    # rms of output rows ~= 1
    rms = np.sqrt(np.mean(np.asarray(out) ** 2, axis=-1))
    np.testing.assert_allclose(rms, 1.0, atol=1e-3)
