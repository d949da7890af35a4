"""Chaos suite: fault-injected solves must recover bit-exactly.

Every test here kills the system somewhere — an ingest batch (before or
after its ring-buffer write), a distributed shard round, a kernel launch
— and asserts the recovered labels are *bit-identical* to the fault-free
oracle.  Run with ``-m chaos`` (the CI chaos job); the suite is also part
of the plain tier-1 run.
"""
import dataclasses
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.checkpoint.manager import CheckpointManager
from repro.connectivity import (
    FaultInjector,
    SolveOptions,
    StreamingConnectivity,
    get_solver,
    register_solver,
    resilient_distributed_contour,
    solve,
    stream_with_recovery,
)
from repro.connectivity import streaming as streaming_mod
from repro.connectivity.solvers import _contour_solver
from repro.data.dedup import StreamingDedup
from repro.data.pipeline import make_corpus
from repro.graphs import generators as gen
from repro.graphs.oracle import connected_components_oracle
from repro.runtime.recovery import (
    ShardLossFault,
    SimulatedFault,
    run_with_recovery,
)

pytestmark = pytest.mark.chaos

SRC = os.path.join(os.path.dirname(__file__), "..", "src")
XLA = SolveOptions(backend="xla")


def _stream_fixture(n_batches=12, seed=0):
    """(graph, oracle, batches): a shuffled micro-batch stream."""
    g = gen.components_mix([gen.path(300, seed=1), gen.rmat(9, seed=2)],
                           seed=3)
    oracle = connected_components_oracle(*g.to_numpy())
    src, dst, n = g.to_numpy()
    m = len(src)
    perm = np.random.default_rng(seed).permutation(m)
    src, dst = src[perm], dst[perm]
    batches = [(src[b * m // n_batches:(b + 1) * m // n_batches],
                dst[b * m // n_batches:(b + 1) * m // n_batches])
               for b in range(n_batches)]
    return g, oracle, batches


# -- checkpointable streaming + crash-restart driver ---------------------

def test_stream_crash_recovery_bitexact(tmp_path):
    """Faults at arbitrary batches/sites == fault-free run, bit for bit."""
    g, oracle, batches = _stream_fixture()

    clean = StreamingConnectivity(g.n_vertices, XLA)
    for b in batches:
        clean.ingest(*b)

    mgr = CheckpointManager(str(tmp_path), keep=3, async_save=False)
    inj = FaultInjector(fail_at=(3, (7, "post_write"), (9, "pre")))
    events = []
    eng, stats = stream_with_recovery(
        batches, g.n_vertices, mgr, XLA, checkpoint_every=3,
        fault_injector=inj, on_event=lambda ev, k: events.append((ev, k)))
    assert stats["restarts"] == 3
    assert stats["checkpoints"] >= 4
    assert [ev for ev, _ in events] == ["restart"] * 3
    snap = eng.snapshot()
    assert bool(snap.converged)
    assert (np.asarray(snap.labels) == oracle).all()
    assert (np.asarray(snap.labels) == np.asarray(clean.labels)).all()
    # the replayed store is byte-identical too, not just the labels
    assert eng.n_edges == clean.n_edges
    assert (np.asarray(eng.graph().src) == np.asarray(clean.graph().src)).all()


def test_stream_recovery_resumes_across_processes(tmp_path):
    """A restart budget blow-through == process death; a second driver
    invocation against the same checkpoint dir resumes, not replays."""
    g, oracle, batches = _stream_fixture()
    mgr = CheckpointManager(str(tmp_path), keep=3, async_save=False)
    inj = FaultInjector(fail_at=(7,))
    with pytest.raises(SimulatedFault):
        stream_with_recovery(batches, g.n_vertices, mgr, XLA,
                             checkpoint_every=3, max_restarts=0,
                             fault_injector=inj)
    assert mgr.latest_step() == 6  # step 6 == resume at batch 6
    eng, stats = stream_with_recovery(batches, g.n_vertices, mgr, XLA,
                                      checkpoint_every=3)
    assert stats["restarts"] == 0
    assert eng.n_batches == len(batches)
    assert (np.asarray(eng.snapshot().labels) == oracle).all()


def test_engine_state_roundtrip_bitexact(tmp_path):
    """save()/restore() round-trips the full engine state mid-stream."""
    g, oracle, batches = _stream_fixture()
    mgr = CheckpointManager(str(tmp_path), keep=2, async_save=False)
    e1 = StreamingConnectivity(g.n_vertices, XLA)
    for b in batches[:6]:
        e1.ingest(*b)
    step = e1.save(mgr)
    assert step == 6
    e2, step2 = StreamingConnectivity.restore(mgr, XLA)
    assert step2 == 6
    assert e2.n_vertices == e1.n_vertices
    assert e2.n_edges == e1.n_edges
    assert e2.capacity == e1.capacity
    assert (np.asarray(e2.labels) == np.asarray(e1.labels)).all()
    # both continuations land on the oracle, bit-identically to each other
    for b in batches[6:]:
        e1.ingest(*b)
        e2.ingest(*b)
    assert (np.asarray(e1.labels) == np.asarray(e2.labels)).all()
    assert (np.asarray(e2.labels) == oracle).all()
    assert float(e1.snapshot().edges_visited) == \
        float(e2.snapshot().edges_visited)


def test_restore_rejects_corrupt_state(tmp_path):
    g, _, batches = _stream_fixture()
    eng = StreamingConnectivity(g.n_vertices, XLA)
    eng.ingest(*batches[0])
    state = eng.state_dict()
    bad = dict(state, n_cap=np.int64(int(state["n_cap"]) * 2))
    with pytest.raises(ValueError, match="corrupt checkpoint"):
        StreamingConnectivity(g.n_vertices, XLA).load_state_dict(bad)
    with pytest.raises(ValueError, match="missing"):
        StreamingConnectivity(g.n_vertices, XLA).load_state_dict(
            {k: v for k, v in state.items() if k != "labels"})


# -- ingest atomicity under mid-ingest faults ----------------------------

def test_ingest_rollback_post_write():
    """A fault after the ring write but before the commit leaves the
    engine queryable with its pre-ingest snapshots (satellite regression:
    the write lands at offset >= m, invisible until the commit)."""
    g, oracle, batches = _stream_fixture()
    eng = StreamingConnectivity(g.n_vertices, XLA,
                                fault_injector=FaultInjector(
                                    fail_at=((1, "post_write"),)))
    eng.ingest(*batches[0])
    before = np.asarray(eng.snapshot().labels).copy()
    m_before, nb_before = eng.n_edges, eng.n_batches
    visited_before = float(eng.snapshot().edges_visited)
    with pytest.raises(SimulatedFault):
        eng.ingest(*batches[1])
    assert eng.n_edges == m_before
    assert eng.n_batches == nb_before
    assert (np.asarray(eng.snapshot().labels) == before).all()
    assert float(eng.snapshot().edges_visited) == visited_before
    # the injector fired once; the replayed batch commits and the stream
    # finishes on the oracle
    for b in batches[1:]:
        eng.ingest(*b)
    assert (np.asarray(eng.snapshot().labels) == oracle).all()


def test_ingest_rollback_after_vertex_growth():
    """Mid-ingest failure rolls back vertex growth too: the engine answers
    queries as if the failed batch (and its new vertices) never arrived."""
    eng = StreamingConnectivity(4, XLA,
                                fault_injector=FaultInjector(
                                    fail_at=((1, "pre"),
                                             (1, "post_write"))))
    eng.ingest([0, 1], [1, 2])
    # growth + pre-solve fault (before any device work)
    with pytest.raises(SimulatedFault):
        eng.ingest([5], [6], n_vertices=8)
    assert eng.n_vertices == 4
    assert eng.snapshot().n_components == 2  # {0,1,2}, {3}
    # growth + post-write fault (batch in the ring at offset >= m,
    # invisible because the commit never ran)
    with pytest.raises(SimulatedFault):
        eng.ingest([2, 8], [3, 9], n_vertices=10)
    assert eng.n_vertices == 4
    assert eng.n_edges == 2
    assert eng.snapshot().n_components == 2
    # replay: the injector fired once per site, so the grown ingest
    # commits for real
    eng.ingest([2, 8], [3, 9], n_vertices=10)
    assert eng.n_vertices == 10
    assert eng.same_component(0, 3)
    assert eng.same_component(8, 9)
    assert not eng.same_component(0, 8)


# -- run_with_recovery: configurable recoverable set + backoff -----------

def test_run_with_recovery_recoverable_set(tmp_path):
    """Real faults (RuntimeError) restore when configured; the default
    conservative set still lets them propagate (satellite regression)."""
    def make_step(fail_once_at):
        fired = set()

        def step(state, k):
            if k == fail_once_at and k not in fired:
                fired.add(k)
                raise RuntimeError("transient XLA failure")
            out = state.copy()
            out[k] += 1  # counts executions: replay must not double-apply
            return out
        return step

    init = np.zeros(10, np.int64)
    mgr = CheckpointManager(str(tmp_path / "a"), async_save=False)
    with pytest.raises(RuntimeError):
        run_with_recovery(make_step(5), init, 10, mgr, checkpoint_every=3)

    mgr = CheckpointManager(str(tmp_path / "b"), async_save=False)
    state, stats = run_with_recovery(
        make_step(5), init, 10, mgr, checkpoint_every=3,
        recoverable=(RuntimeError,))
    assert stats["restarts"] == 1
    # restored-then-replayed state is exactly one application per step
    assert (np.asarray(state) == 1).all()


def test_run_with_recovery_backoff_schedule(tmp_path):
    delays = []
    inj = FaultInjector(fail_at=(2, 5, 8))
    mgr = CheckpointManager(str(tmp_path), async_save=False)
    _, stats = run_with_recovery(
        lambda s, k: s + 1, 0, 10, mgr, checkpoint_every=4,
        fault_injector=inj, backoff_base=0.5, backoff_factor=2.0,
        backoff_cap=1.5, sleep_fn=delays.append)
    assert stats["restarts"] == 3
    assert delays == [0.5, 1.0, 1.5]  # exponential, capped


def test_run_with_recovery_budget_exhaustion(tmp_path):
    inj = FaultInjector(fail_at=(1, 2, 3))
    mgr = CheckpointManager(str(tmp_path), async_save=False)
    with pytest.raises(SimulatedFault):
        run_with_recovery(lambda s, k: s, 0, 5, mgr, checkpoint_every=2,
                          max_restarts=2, fault_injector=inj)


# -- graceful degradation: kernel launch failure -> XLA fallback ---------

@pytest.fixture
def flaky_solver():
    """A contour clone whose non-XLA backends always fail to launch."""
    base = get_solver("contour")

    def flaky_fn(graph, opts, init):
        if opts.backend != "xla":
            raise RuntimeError("fake kernel launch failure")
        return _contour_solver(graph, opts, init)

    register_solver(dataclasses.replace(base, name="flaky", fn=flaky_fn,
                                        aliases=()))
    yield "flaky"
    from repro.connectivity.registry import _REGISTRY
    _REGISTRY.pop("flaky", None)


def test_solve_kernel_fallback(flaky_solver):
    g = gen.path(200, seed=1)
    oracle = connected_components_oracle(*g.to_numpy())
    res = solve(g, algorithm=flaky_solver, backend="pallas_blocked")
    assert (np.asarray(res.labels) == oracle).all()
    assert res.provenance is not None
    assert res.provenance[0].startswith("kernel_fallback:pallas_blocked")
    # a clean solve records its resolved plan, but no degradation events
    clean = solve(g, backend="xla").provenance
    assert not [p for p in clean if p.startswith("kernel_fallback")]
    assert [p for p in clean if p.startswith("plan:xla")]
    # opting out fails loudly
    with pytest.raises(RuntimeError, match="fake kernel"):
        solve(g, algorithm=flaky_solver, backend="pallas_blocked",
              kernel_fallback=False)


def test_solve_fallback_never_masks_caller_bugs():
    """Non-transient errors and injected machine faults must propagate:
    a ValueError is a caller bug, and a SimulatedFault must reach the
    checkpoint/restore layer, never be absorbed as a kernel retry."""
    base = get_solver("contour")

    def buggy_fn(graph, opts, init):
        if opts.backend != "xla":
            raise ValueError("caller bug, not a launch failure")
        return _contour_solver(graph, opts, init)

    def faulty_fn(graph, opts, init):
        if opts.backend != "xla":
            raise SimulatedFault("injected machine fault")
        return _contour_solver(graph, opts, init)

    from repro.connectivity.registry import _REGISTRY
    g = gen.path(50, seed=1)
    try:
        register_solver(dataclasses.replace(base, name="buggy", fn=buggy_fn,
                                            aliases=()))
        register_solver(dataclasses.replace(base, name="faulty",
                                            fn=faulty_fn, aliases=()))
        # if either were (wrongly) retried on XLA it would *succeed* and
        # return a fallback-provenance result instead of raising
        with pytest.raises(ValueError, match="caller bug"):
            solve(g, algorithm="buggy", backend="pallas_blocked")
        with pytest.raises(SimulatedFault):
            solve(g, algorithm="faulty", backend="pallas_blocked")
    finally:
        _REGISTRY.pop("buggy", None)
        _REGISTRY.pop("faulty", None)


def _mosaic_error():
    from jax._src.pallas.mosaic.error_handling import MosaicError
    return MosaicError("infer-vector-layout: unsupported shape cast")


def _mosaic_compile_error():
    import jax
    return jax.errors.JaxRuntimeError(
        "INVALID_ARGUMENT: Mosaic failed to compile TPU kernel: Failed to "
        "verify layout for Mosaic kernel operand 3")


@pytest.mark.parametrize("make_exc", [_mosaic_error, _mosaic_compile_error],
                         ids=["lowering", "compile"])
def test_solve_kernel_build_failure_is_not_retried(make_exc):
    """A kernel that fails to lower or compile is a program bug: even with
    kernel_fallback on, solve() raises instead of rerunning on XLA."""
    base = get_solver("contour")
    calls = []

    def broken_fn(graph, opts, init):
        calls.append(opts.backend)
        if opts.backend != "xla":
            raise make_exc()
        return _contour_solver(graph, opts, init)

    from repro.connectivity.registry import _REGISTRY
    g = gen.path(50, seed=1)
    try:
        register_solver(dataclasses.replace(base, name="broken",
                                            fn=broken_fn, aliases=()))
        with pytest.raises(type(make_exc())):
            solve(g, algorithm="broken", backend="pallas_blocked",
                  kernel_fallback=True)
    finally:
        _REGISTRY.pop("broken", None)
    assert calls == ["pallas_blocked"]  # no XLA rerun


def test_streaming_kernel_build_failure_is_not_retried(monkeypatch):
    g, _, batches = _stream_fixture(n_batches=2)
    real = streaming_mod.delta_converge

    def fake(*args, **kw):
        if kw.get("backend") != "xla":
            raise _mosaic_compile_error()
        return real(*args, **kw)

    monkeypatch.setattr(streaming_mod, "delta_converge", fake)
    eng = StreamingConnectivity(g.n_vertices,
                                SolveOptions(backend="pallas_blocked"))
    with pytest.raises(RuntimeError, match="Mosaic failed to compile"):
        eng.ingest(*batches[0])
    assert eng.n_edges == 0


def test_streaming_kernel_fallback(monkeypatch):
    g, oracle, batches = _stream_fixture(n_batches=4)
    real = streaming_mod.delta_converge

    def fake(*args, **kw):
        if kw.get("backend") != "xla":
            raise RuntimeError("fake kernel launch failure")
        return real(*args, **kw)

    monkeypatch.setattr(streaming_mod, "delta_converge", fake)
    eng = StreamingConnectivity(g.n_vertices,
                                SolveOptions(backend="pallas_blocked"))
    for b in batches:
        eng.ingest(*b)
    snap = eng.snapshot()
    assert (np.asarray(snap.labels) == oracle).all()
    fallbacks = [p for p in snap.provenance
                 if p.startswith("kernel_fallback")]
    assert len(fallbacks) == len(batches)
    assert all(p.startswith("kernel_fallback:pallas_blocked")
               for p in fallbacks)
    # the retry's resolved plan is recorded alongside the events
    assert [p for p in snap.provenance if p.startswith("plan:")]

    eng = StreamingConnectivity(g.n_vertices,
                                SolveOptions(backend="pallas_blocked",
                                             kernel_fallback=False))
    with pytest.raises(RuntimeError, match="fake kernel"):
        eng.ingest(*batches[0])
    assert eng.n_edges == 0  # atomic: nothing committed


# -- straggler-driven checkpoint cadence ---------------------------------

class _ScriptedMonitor:
    """StragglerMonitor stand-in returning a scripted action sequence."""

    def __init__(self, actions):
        self.actions = list(actions)

    def start_step(self):
        pass

    def end_step(self):
        return self.actions.pop(0)


def test_straggler_forces_checkpoint(tmp_path):
    g, oracle, batches = _stream_fixture(n_batches=6)
    mgr = CheckpointManager(str(tmp_path), keep=5, async_save=False)
    # cadence alone would checkpoint only at batch 6; the monitor flags
    # batch 1 as persistently slow -> snapshot now, losing no work
    monitor = _ScriptedMonitor(["ok", "checkpoint", "ok", "ok", "ok", "ok"])
    steps_seen = []
    orig_save = mgr.save

    def spy(step, state):
        steps_seen.append(step)
        return orig_save(step, state)

    mgr.save = spy
    eng, stats = stream_with_recovery(batches, g.n_vertices, mgr, XLA,
                                      checkpoint_every=6, straggler=monitor)
    assert stats["straggler_events"] == 1
    assert steps_seen == [2, 6]  # forced at committed=2, cadence at end
    assert (np.asarray(eng.snapshot().labels) == oracle).all()


# -- elastic shrink-and-resume (distributed) -----------------------------

def test_resilient_distributed_single_device(tmp_path):
    """Plain fault on a 1-device mesh: warm restart from the manager's
    last checkpoint, fixed point bit-identical to the oracle."""
    g, oracle, _ = _stream_fixture()
    mgr = CheckpointManager(str(tmp_path), async_save=False)
    inj = FaultInjector(fail_at=((1, "round"),))
    res, stats = resilient_distributed_contour(
        g, options=XLA, block_rounds=2, fault_injector=inj, manager=mgr)
    assert stats["restarts"] == 1
    assert stats["shrinks"] == 0
    assert bool(res.converged)
    assert (np.asarray(res.labels) == oracle).all()
    assert mgr.latest_step() is not None  # converged block checkpointed


def test_resilient_distributed_straggler_ladder(tmp_path):
    """'checkpoint' then 'evict' escalation on a 1-device mesh: both
    force a label snapshot; eviction cannot shrink below the model-
    parallel floor, so the solve degrades gracefully instead of dying."""
    g, oracle, _ = _stream_fixture()
    mgr = CheckpointManager(str(tmp_path), async_save=False)
    monitor = _ScriptedMonitor(["checkpoint", "evict"] + ["ok"] * 50)
    res, stats = resilient_distributed_contour(
        g, options=XLA, block_rounds=4, straggler=monitor, manager=mgr)
    assert bool(res.converged)
    assert (np.asarray(res.labels) == oracle).all()
    assert stats["shrinks"] == 0  # 1 device: eviction floor holds
    assert stats["checkpoints"] >= 2  # forced blocks (+ converged block)
    assert ("straggler_checkpoint", 0) in stats["events"]
    assert mgr.latest_step() is not None


def test_resilient_budget_exhaustion_not_converged():
    """Running out of the round budget reports converged=False (and the
    partial labels are still a sound warm start)."""
    g, oracle, _ = _stream_fixture()
    res, stats = resilient_distributed_contour(
        g, options=XLA.replace(max_iters=1), block_rounds=1)
    assert not bool(res.converged)
    res2 = solve(g, XLA, warm_start=res)
    assert (np.asarray(res2.labels) == oracle).all()


_SHRINK_SUBPROCESS = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import numpy as np
    import jax
    from repro.connectivity import (SolveOptions, FaultInjector,
                                    resilient_distributed_contour)
    from repro.runtime.recovery import ShardLossFault
    from repro.graphs import generators as gen
    from repro.graphs.oracle import connected_components_oracle

    g = gen.components_mix([gen.path(2000, seed=1), gen.rmat(10, seed=2)],
                           seed=3)
    oracle = connected_components_oracle(*g.to_numpy())

    # lose one shard at round-block 1, another at block 2: 8 -> 7 -> 6
    inj = FaultInjector(fail_at=((1, "round"), (2, "round")),
                        exc_factory=lambda step, site: ShardLossFault(1))
    res, stats = resilient_distributed_contour(
        g, devices=jax.devices(), options=SolveOptions(backend="xla"),
        block_rounds=2, fault_injector=inj)
    assert stats["shrinks"] == 2, stats
    assert stats["mesh_history"] == [(8, 1), (7, 1), (6, 1)], stats
    assert bool(res.converged), stats
    assert (np.asarray(res.labels) == oracle).all()
    assert res.provenance[0].startswith("plan:xla")  # resolved plan leads
    assert res.provenance[1:] == ("elastic_shrink:8->7",
                                  "elastic_shrink:7->6")
    print("SHRINK_OK", dict(stats))
""")


def test_elastic_shrink_8way_subprocess():
    """Shard loss mid-solve on a real 8-way mesh: shrink to 7 then 6
    shards, warm-resume, converge to the fault-free fixed point."""
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", _SHRINK_SUBPROCESS],
                         capture_output=True, text=True, env=env,
                         timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "SHRINK_OK" in out.stdout


# -- dedup state checkpointing -------------------------------------------

def test_streaming_dedup_state_roundtrip():
    """StreamingDedup checkpoints its LSH buckets + engine state; a
    restored instance continues bit-identically."""
    docs = make_corpus(n_docs=120, doc_len=80, vocab_size=500,
                       dup_fraction=0.3, near_dup_noise=0.03, seed=7)
    d1 = StreamingDedup(n_hashes=32, bands=8)
    for pos in range(0, 60, 20):
        d1.add_docs(docs[pos:pos + 20])
    state = d1.state_dict()

    d2 = StreamingDedup(n_hashes=32, bands=8).load_state_dict(state)
    assert d2.n_docs == d1.n_docs
    assert d2.n_candidate_pairs == d1.n_candidate_pairs
    for pos in range(60, 120, 20):
        d1.add_docs(docs[pos:pos + 20])
        d2.add_docs(docs[pos:pos + 20])
    assert (d1.labels() == d2.labels()).all()
    r1, r2 = d1.report(), d2.report()
    assert r1.n_clusters == r2.n_clusters
    assert (r1.keep == r2.keep).all()
