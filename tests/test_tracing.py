"""The program's trace names: device scopes in every lowered program, host
spans around ``solve()`` and ``ingest()`` in a recorded trace."""
import glob
import json
import os
import re
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro
from repro import tracing
from repro.connectivity import streaming
from repro.connectivity.contour import contour_labels
from repro.connectivity.distributed import _distributed_fixpoint
from repro.connectivity.planner import heuristic_plan
from repro.graphs import generators as gen

N, M = 300, 1200
SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _locations(lowered) -> set:
    """The name paths of a lowered program's operations."""
    return set(re.findall(r'loc\("([^"]*)"', lowered.as_text(
        debug_info=True)))


def _under(locs, *path) -> bool:
    """Some operation lies under ``path`` (scopes, outermost first)."""
    want = "/" + "/".join(path) + "/"
    return any(want in "/" + loc for loc in locs)


def _edges(n=N, m=M, seed=0):
    rng = np.random.default_rng(seed)
    return (jnp.asarray(rng.integers(0, n, m), jnp.int32),
            jnp.asarray(rng.integers(0, n, m), jnp.int32))


@pytest.mark.parametrize("sweep", ["pallas_blocked", "xla", "fused"])
def test_solve_program_carries_every_scope_of_its_sweep(sweep):
    src, dst = _edges()
    plan = heuristic_plan(N, M, "tpu").replace(interpret=True)
    backend = "xla" if sweep == "xla" else "pallas_blocked"
    plan = plan.replace(fuse_relabel=sweep == "fused")
    locs = _locations(contour_labels.lower(src, dst, N, backend=backend,
                                           plan=plan))
    body = ("jit(contour_labels)", "while", "body")
    inner = {"pallas_blocked": [tracing.UPDATE_STREAM, tracing.BINNING,
                                tracing.SCATTER_KERNEL],
             "xla": [tracing.SCATTER_MIN],
             "fused": [tracing.FUSED_RELAX]}[sweep]
    for scope in inner:
        assert _under(locs, *body, tracing.MM_SWEEP, scope), scope
    assert _under(locs, *body, tracing.POINTER_JUMP)
    assert _under(locs, *body, tracing.CONVERGE_CHECK)
    # the final compression after the loop
    assert _under(locs, "jit(contour_labels)", tracing.POINTER_JUMP)
    if sweep == "pallas_blocked":
        # the Mosaic kernel carries its name under its scope
        assert _under(locs, tracing.MM_SWEEP, tracing.SCATTER_KERNEL,
                      tracing.SCATTER_KERNEL_NAME)
    # no other sweep's scope leaks in
    others = {tracing.UPDATE_STREAM, tracing.BINNING, tracing.SCATTER_KERNEL,
              tracing.SCATTER_MIN, tracing.FUSED_RELAX} - set(inner)
    assert not any(_under(locs, tracing.MM_SWEEP, s) for s in others)


def test_adaptive_solve_scopes_sampling_compaction_and_compression():
    src, dst = _edges()
    locs = _locations(contour_labels.lower(
        src, dst, N, sampling=2, compact_every=1, sampling_strategy="kout"))
    top = "jit(contour_labels)"
    assert _under(locs, top, tracing.SAMPLING_PREP)
    assert _under(locs, top, "while", "body", tracing.MM_SWEEP,
                  tracing.SCATTER_MIN)
    assert _under(locs, top, "while", "body", tracing.CONVERGE_CHECK)
    assert _under(locs, top, "while", "body", tracing.COMPACT)
    assert _under(locs, top, tracing.COMPRESS, "while", "body",
                  tracing.POINTER_JUMP)


@pytest.mark.parametrize("backend", ["pallas_blocked", "xla"])
def test_delta_solve_and_stream_helpers_carry_their_scopes(backend):
    k, n = 64, 512
    src, dst = _edges(n, k, seed=1)
    plan = heuristic_plan(n, k, "tpu").replace(interpret=True,
                                               fuse_relabel=False)
    locs = _locations(streaming.delta_converge.lower(
        src, dst, jnp.arange(n, dtype=jnp.int32), jnp.int32(k - 3),
        backend=backend, plan=plan))
    top = "jit(delta_converge)"
    assert _under(locs, top, tracing.SUPERVERTEX_REWRITE)
    body = (top, "while", "body")
    assert _under(locs, *body, tracing.MM_SWEEP)
    assert _under(locs, *body, tracing.POINTER_JUMP)
    assert _under(locs, *body, tracing.CONVERGE_CHECK)
    assert _under(locs, *body, tracing.COMPACT)
    assert _under(locs, top, tracing.COMPRESS)
    if backend == "pallas_blocked":
        assert _under(locs, *body, tracing.MM_SWEEP, tracing.BINNING)
    pad = _locations(streaming._pad_batch.lower(src[:50], dst[:50],
                                                pad_k=64))
    assert _under(pad, "jit(_pad_batch)", tracing.PAD)
    store = _locations(streaming._ring_write.lower(
        jnp.zeros((256,), jnp.int32), src, jnp.int32(7)))
    assert _under(store, "jit(_ring_write)", tracing.STORE)


def test_sharded_solve_scopes_its_label_allreduce():
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:1]), ("data",))
    src, dst = _edges()
    locs = _locations(_distributed_fixpoint.lower(
        src, dst, jnp.arange(N, dtype=jnp.int32), jnp.int32(M), mesh=mesh,
        edge_axes=("data",), local_rounds=1, max_iters=50, async_compress=1,
        backend="xla", plan=None, sampling=0, compact_every=0))
    assert _under(locs, tracing.LABEL_ALLREDUCE)
    assert _under(locs, tracing.MM_SWEEP, tracing.SCATTER_MIN)


# Compiles the sharded solve's programs for a mesh of four CPU devices and
# prints the op_name of every all-reduce in their HLO, one JSON list per
# program: _distributed_fixpoint as solve(graph, mesh=...) calls it by
# default, and distributed_contour_step_fn with the test every round and
# every other round.
_SHARDED_ALL_REDUCES = textwrap.dedent("""
    import json, os, re
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro import jax_compat
    from repro.connectivity.distributed import (
        _distributed_fixpoint, distributed_contour_step_fn)
    from repro.connectivity.planner import resolve_plan

    mesh = jax_compat.make_mesh((4,), ("data",))
    n, m = 300, 1200
    edges = NamedSharding(mesh, P("data"))
    rep = NamedSharding(mesh, P())
    src = jax.device_put(jnp.arange(m, dtype=jnp.int32) % n, edges)
    dst = jax.device_put(jnp.arange(m, dtype=jnp.int32)[::-1] % n, edges)
    plan = resolve_plan(n, m // 4)
    programs = [_distributed_fixpoint.lower(
        src, dst, jax.device_put(jnp.arange(n, dtype=jnp.int32), rep),
        jax.device_put(jnp.int32(m), rep), mesh=mesh, edge_axes=("data",),
        local_rounds=1, max_iters=100, async_compress=1,
        backend=plan.backend, plan=plan, sampling=0, compact_every=0)]
    programs += [distributed_contour_step_fn.lower(
        src, dst, n, mesh, check_every=k) for k in (1, 2)]
    for lowered in programs:
        hlo = lowered.compile().as_text()
        print(json.dumps([
            (re.search(r'op_name="([^"]*)"', line) or [None, None])[1]
            for line in hlo.splitlines()
            if " all-reduce(" in line or " all-reduce-start(" in line]))
""")


def test_every_all_reduce_of_a_sharded_solve_is_scoped():
    """On a mesh of four devices, the round's two collectives, the label
    ``pmin`` and the shards' AND-reduce of the convergence test, each lie
    under a scope: no collective of the round goes unattributed."""
    env = dict(os.environ, PYTHONPATH=os.pathsep.join(
        filter(None, [SRC, os.environ.get("PYTHONPATH")])))
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", _SHARDED_ALL_REDUCES],
                         capture_output=True, text=True, env=env,
                         timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    programs = [json.loads(line) for line in out.stdout.splitlines()
                if line.startswith("[")]
    assert len(programs) == 3
    for names in programs:
        assert names, "no all-reduce in the sharded program"
        for name in names:
            assert name and (f"/{tracing.LABEL_ALLREDUCE}/" in name
                             or f"/{tracing.CONVERGE_CHECK}/" in name), name
        assert any(f"/{tracing.LABEL_ALLREDUCE}/" in n for n in names)
        assert any(f"/{tracing.CONVERGE_CHECK}/" in n for n in names)


def test_every_scope_and_span_is_named_once():
    assert len(set(tracing.DEVICE_SCOPES)) == len(tracing.DEVICE_SCOPES)
    assert len(set(tracing.HOST_SPANS)) == len(tracing.HOST_SPANS)
    assert all(s.startswith("repro.") for s in tracing.HOST_SPANS)
    assert not set(tracing.DEVICE_SCOPES) & set(tracing.HOST_SPANS)


def _host_spans(log_dir) -> list:
    """``(name, stats, start_ns, end_ns)`` of the ``repro.*`` events of a
    recorded trace, names cut at any ``#`` argument suffix."""
    path = glob.glob(f"{log_dir}/plugins/profile/*/*.xplane.pb")[0]
    out = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                name = ev.name.split("#")[0]
                if name.startswith("repro."):
                    out.append((name, dict(ev.stats), ev.start_ns,
                                ev.start_ns + ev.duration_ns))
    return sorted(out, key=lambda s: (s[2], -s[3]))


def _children(spans, parent) -> list:
    _, _, lo, hi = parent
    return [s[0] for s in spans
            if s is not parent and s[2] >= lo and s[3] <= hi]


def test_solve_and_ingest_spans_nest_and_carry_their_arguments(tmp_path):
    g = gen.path(64)
    s = repro.StreamingConnectivity(64)
    with jax.profiler.trace(str(tmp_path)):
        repro.solve(g).labels.block_until_ready()
        s.ingest(np.array([0, 1, 2]), np.array([1, 2, 3]))
        s.ingest(np.array([5, 6]), np.array([6, 7]))
        s.labels.block_until_ready()
    spans = _host_spans(tmp_path)
    names = [n for n, *_ in spans]
    assert set(names) <= set(tracing.HOST_SPANS)

    solves = [sp for sp in spans if sp[0] == tracing.SOLVE]
    assert len(solves) == 1 and "call" in solves[0][1]
    assert _children(spans, solves[0]) == [
        tracing.SOLVE_PLAN, tracing.SOLVE_DISPATCH, tracing.SOLVE_RESULT]

    ingests = [sp for sp in spans if sp[0] == tracing.INGEST]
    assert [int(sp[1]["batch"]) for sp in ingests] == [0, 1]
    for sp in ingests:
        assert _children(spans, sp) == [
            tracing.INGEST_VALIDATE, tracing.INGEST_PAD, tracing.INGEST_PLAN,
            tracing.INGEST_DELTA_SOLVE, tracing.INGEST_STORE,
            tracing.INGEST_COMMIT]


def test_solve_calls_are_numbered_in_order(tmp_path):
    g = gen.path(16)
    with jax.profiler.trace(str(tmp_path)):
        for _ in range(3):
            repro.solve(g).labels.block_until_ready()
    calls = [int(st["call"]) for n, st, _, _ in _host_spans(tmp_path)
             if n == tracing.SOLVE]
    assert len(calls) == 3 and calls == sorted(calls)
    assert calls[2] - calls[0] == 2

