"""Compile-only rehearsals of the main-path kernels for a TPU v5e.

The TPU compiler is installed alongside jax, and it compiles for a chip
that is described rather than attached.  These tests lower and compile
the kernels the planner sends a TPU, at the sizes users run, so Mosaic
rejects a bad block shape, layout or memory budget here instead of on the
chip.  Nothing runs: results are checked by the interpret-mode tests.

The topology is described inside a fixture, never at import: only one
process may load the TPU library at a time, and every test worker
imports this file.
"""
import contextlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro import jax_compat
from repro.connectivity.distributed import _distributed_fixpoint
from repro.connectivity.planner import heuristic_plan
from repro.kernels.contour_mm.blocked import (binned_scatter_min_pallas,
                                              fused_relax_pallas)

# Graph500 scale 22, edge factor 16: vertices and deduplicated edges of
# generators.rmat(22, edge_factor=16, seed=0), the chip smoke's graph.
G500_22_N = 1 << 22
G500_22_M = 64_151_199


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def no_compile_cache():
    """Compiles for a described chip cannot be read back from JAX's
    persistent cache; keep them out of it."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _i32(shape, sharding):
    return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=sharding)


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("n_vertices", [G500_22_N, 1 << 27],
                         ids=["graph500-22", "n2^27"])
def test_binned_scatter_min_compiles_at_graph500_22_stream(one_chip,
                                                           n_vertices):
    """The blocked sweep at the tiles the TPU table picks, on the order-2
    update stream of graph500-22 (4m updates)."""
    plan = heuristic_plan(n_vertices, G500_22_M, "tpu")
    assert plan.backend == "pallas_blocked" and not plan.fuse_relabel
    k = 4 * G500_22_M

    def sweep(L, t, v):
        return binned_scatter_min_pallas(
            L, t, v, label_block=plan.label_block,
            chunk_updates=plan.chunk_updates, interpret=False,
            valid=t > 0)

    compiled = jax.jit(sweep).lower(
        _i32((n_vertices,), one_chip), _i32((k,), one_chip),
        _i32((k,), one_chip)).compile()
    _assert_kernel(compiled)


def test_fused_relax_compiles_at_single_tile_ceiling(one_chip):
    n = 4096
    plan = heuristic_plan(n, 16 * n, "tpu")
    assert plan.fuse_relabel

    def sweep(L, s, d):
        return fused_relax_pallas(L, s, d, chunk_edges=plan.chunk_updates,
                                  interpret=False,
                                  edge_limit=jnp.int32(16 * n - 5))

    compiled = jax.jit(sweep).lower(
        _i32((n,), one_chip), _i32((16 * n,), one_chip),
        _i32((16 * n,), one_chip)).compile()
    _assert_kernel(compiled)


def test_sharded_fixpoint_compiles_with_blocked_kernel(topo):
    """The mesh solve's per-shard sweep runs the blocked kernel inside
    shard_map (check_vma on), with the per-round label all-reduce."""
    mesh = jax_compat.device_mesh(np.array(topo.devices), ("data",))
    n, m = 1 << 14, 1 << 16
    edges = NamedSharding(mesh, P("data"))
    rep = NamedSharding(mesh, P())
    compiled = _distributed_fixpoint.lower(
        _i32((m,), edges), _i32((m,), edges), _i32((n,), rep),
        _i32((), rep), mesh=mesh, edge_axes=("data",), local_rounds=1,
        max_iters=100, async_compress=1, backend="pallas_blocked",
        plan=heuristic_plan(n, m, "tpu"), sampling=0,
        compact_every=0).compile()
    txt = compiled.as_text()
    assert "tpu_custom_call" in txt
    assert "all-reduce" in txt
