"""Distributed Contour: shard_map edge-parallel execution.

Multi-device coverage runs in a subprocess with
XLA_FLAGS=--xla_force_host_platform_device_count=8 (per the assignment,
the test process itself must keep seeing 1 device).
"""
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

import jax

from repro import jax_compat
from repro.core.distributed import distributed_contour
from repro.graphs import generators as gen
from repro.graphs.oracle import connected_components_oracle

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def test_distributed_single_device_mesh():
    """Degenerate 1-device mesh: the shard_map path must still be exact."""
    mesh = jax_compat.device_mesh(np.array(jax.devices()[:1]), ("data",))
    g = gen.components_mix([gen.path(400, seed=1), gen.rmat(9, seed=2)],
                           seed=3)
    oracle = connected_components_oracle(*g.to_numpy())
    labels, rounds = distributed_contour(g, mesh, edge_axes=("data",))
    assert (np.asarray(labels) == oracle).all()
    assert int(rounds) >= 1


_SUBPROCESS_BODY = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import numpy as np
    import jax
    from repro import jax_compat
    from repro.core.distributed import distributed_contour
    from repro.graphs import generators as gen
    from repro.graphs.oracle import connected_components_oracle

    mesh = jax_compat.make_mesh((8,), ("data",))
    graphs = [
        gen.path(3000, seed=1),
        gen.grid2d(40, 40),
        gen.rmat(11, seed=2),
        gen.components_mix([gen.path(500, seed=3), gen.star(400, seed=4)],
                           seed=5),
    ]
    for g in graphs:
        oracle = connected_components_oracle(*g.to_numpy())
        for lr in (1, 3):
            labels, rounds = distributed_contour(
                g, mesh, edge_axes=("data",), local_rounds=lr)
            assert (np.asarray(labels) == oracle).all(), (g.n_vertices, lr)
            assert int(rounds) >= 1
    # beyond-paper local-iteration mode must reduce global rounds on
    # diameter-bound graphs
    g = gen.path(3000, seed=1)
    _, r1 = distributed_contour(g, mesh, edge_axes=("data",), local_rounds=1)
    _, r3 = distributed_contour(g, mesh, edge_axes=("data",), local_rounds=3)
    assert int(r3) < int(r1), (int(r1), int(r3))
    print("SUBPROCESS_OK", int(r1), int(r3))
""")


@pytest.mark.slow  # spawns a fresh 8-device subprocess (jit recompiles)
def test_distributed_8way_subprocess():
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", _SUBPROCESS_BODY],
                         capture_output=True, text=True, env=env,
                         timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "SUBPROCESS_OK" in out.stdout


_FRONTIER_SUBPROCESS_BODY = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import numpy as np
    import jax
    from repro import jax_compat
    from repro.connectivity.distributed import distributed_contour
    from repro.graphs import generators as gen
    from repro.graphs.oracle import connected_components_oracle

    mesh = jax_compat.make_mesh((8,), ("data",))
    g = gen.components_mix([gen.path(2000, seed=1), gen.rmat(10, seed=2)],
                           seed=3)
    oracle = connected_components_oracle(*g.to_numpy())
    dense_L, dense_r, dense_ok, dense_v = distributed_contour(
        g, mesh, edge_axes=("data",))
    assert bool(dense_ok)
    assert (np.asarray(dense_L) == oracle).all()
    # the counter reports real edges only — shard padding is never
    # counted on either schedule
    assert float(dense_v) == int(dense_r) * g.n_edges
    for sampling, ce in ((2, 2), (0, 1), (3, 0)):
        L, r, ok, v = distributed_contour(
            g, mesh, edge_axes=("data",), sampling=sampling,
            compact_every=ce)
        assert bool(ok), (sampling, ce)
        # per-shard contraction must not change the fixed point ...
        assert np.array_equal(np.asarray(L), np.asarray(dense_L)), \\
            (sampling, ce)
        # ... while any compacting schedule counts less work per round
        if ce > 0:
            assert float(v) < int(r) * g.n_edges, \\
                (sampling, ce, float(v))
    print("FRONTIER_SUBPROCESS_OK")
""")


@pytest.mark.slow  # spawns a fresh 8-device subprocess (jit recompiles)
def test_distributed_frontier_8way_subprocess():
    """Per-shard work-adaptive contraction (DESIGN.md §10) on a real
    multi-device mesh: bit-identical labels, fewer edges visited."""
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", _FRONTIER_SUBPROCESS_BODY],
                         capture_output=True, text=True, env=env,
                         timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "FRONTIER_SUBPROCESS_OK" in out.stdout


# A solve over four devices with the planner's plan resolution spied on:
# prints the edge counts it was keyed on, the graph's, and its provenance.
_PLAN_KEY_SUBPROCESS_BODY = textwrap.dedent("""
    import json, os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import numpy as np
    import repro
    from repro import jax_compat
    from repro.connectivity import planner
    from repro.graphs.oracle import connected_components_oracle

    keyed = []
    real = planner.resolve_plan

    def spy(n_vertices, m_edges, **kw):
        keyed.append(m_edges)
        return real(n_vertices, m_edges, **kw)

    planner.resolve_plan = spy
    rng = np.random.default_rng(7)
    n, m = 700, 1001            # 1001 edges pad to 4 shards of 251
    g = repro.Graph(src=rng.integers(0, n, m).astype(np.int32),
                    dst=rng.integers(0, n, m).astype(np.int32),
                    n_vertices=n)
    res = repro.solve(g, mesh=jax_compat.make_mesh((4,), ("data",)))
    oracle = connected_components_oracle(*g.to_numpy())
    assert (np.asarray(res.labels) == oracle).all()
    print(json.dumps({"keyed": keyed, "provenance": list(res.provenance),
                      "shard_plan": real(n, 251).provenance_entry()}))
""")


def test_sharded_plan_is_keyed_on_the_shards_edges():
    """Each device sweeps only its own block of the edges, so the plan of
    a solve over a mesh is resolved for one shard's block: 1001 edges
    over four devices pad to 1004, 251 a shard."""
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", _PLAN_KEY_SUBPROCESS_BODY],
                         capture_output=True, text=True, env=env,
                         timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    got = json.loads(out.stdout.strip().splitlines()[-1])
    assert got["keyed"] and set(got["keyed"]) == {251}
    assert got["shard_plan"] in got["provenance"]
