"""The execution-plan layer: resolution, cache, VMEM, staged/fused
bit-exactness, autotuner determinism, provenance (DESIGN.md §14).

The load-bearing properties, in test order:

* **resolution precedence** — pinned plan > tuning cache (``auto`` only)
  > heuristic tables; forced backends never consult the cache;
* **cache robustness** — round-trips are deterministic; corrupt, stale,
  malformed or expired entries resolve to the heuristic prior and can
  never crash a solve;
* **VMEM ceiling** — derived from the queried/declared budget instead of
  the seed's hard-coded 3M, overridable via ``SolveOptions`` and env,
  with the boundary unit-tested;
* **schedule equivalence** — the physically staged frontier driver and
  the fused relabel+scatter-min pass are bit-exact with the masked/
  unfused realisations (and the oracle);
* **autotuner** — deterministic under an injected measure function,
  hysteresis keeps the prior on near-ties, tuned plans are bit-exact
  with heuristic plans on every backend (``tuning`` marker);
* **provenance** — every planned solve path records the resolved plan.
"""
import json
import os

import numpy as np
import pytest

import jax.numpy as jnp

from repro.connectivity import SolveOptions, solve, solve_batch
from repro.connectivity import planner
from repro.connectivity.contour import contour_labels
from repro.connectivity.planner import (
    SINGLE_TILE_MAX_N,
    XLA_SWEEP_MIN_EDGES,
    ExecutionPlan,
    cache,
    heuristic_plan,
    plan_key,
    resolve_plan,
)
from repro.connectivity.planner import staged as staged_mod
from repro.graphs import generators as gen
from repro.graphs.oracle import connected_components_oracle, labels_equivalent
from repro.kernels.contour_mm import ops as mm_ops


@pytest.fixture()
def tmp_cache(tmp_path):
    return str(tmp_path / "tuning.json")


@pytest.fixture()
def graph():
    return gen.components_mix([gen.path(400, seed=1), gen.rmat(9, seed=2)],
                              seed=3)


# ---------------------------------------------------------------- resolution

def test_heuristic_plan_is_platform_and_size_aware():
    cpu = heuristic_plan(1000, 5000, "cpu")
    assert cpu.backend == "xla" and cpu.interpret
    small = heuristic_plan(1000, 5000, "tpu")
    assert small.backend == "pallas_blocked"
    assert small.fuse_relabel and small.label_block >= 1000
    big = heuristic_plan(1 << 20, 1 << 22, "tpu")
    assert big.backend == "xla"
    assert not big.fuse_relabel and big.label_block == 1024
    edge = heuristic_plan(SINGLE_TILE_MAX_N, 1 << 22, "tpu")
    assert edge.backend == "pallas_blocked" and edge.fuse_relabel
    # above one tile the edge count picks the sweep
    assert heuristic_plan(SINGLE_TILE_MAX_N + 1, XLA_SWEEP_MIN_EDGES,
                          "tpu").backend == "xla"
    few = heuristic_plan(SINGLE_TILE_MAX_N + 1, XLA_SWEEP_MIN_EDGES - 1,
                         "tpu")
    assert few.backend == "pallas_blocked" and not few.fuse_relabel
    assert heuristic_plan(100, 100, "tpu").compact_schedule == "masked"
    assert heuristic_plan(100, 1 << 16, "tpu").compact_schedule == "staged"


@pytest.mark.parametrize("n_vertices,n_edges,backend", [
    (1 << 20, 1 << 24, "xla"),             # graph500-s20.solve
    (1 << 20, 3_145_688, "xla"),           # delaunay-n20.solve
    (1 << 20, 1 << 16, "pallas_blocked"),  # stream-b16: one padded batch
    (1 << 22, 64_151_199, "xla"),          # graph500-22
], ids=["graph500-s20", "delaunay-n20", "stream-b16", "graph500-22"])
def test_tpu_table_backend_at_the_cell_shapes(n_vertices, n_edges, backend):
    """The sweep the chip measured faster at each of these shapes (PERF.md
    section 6); a forced blocked kernel keeps its tiles."""
    plan = heuristic_plan(n_vertices, n_edges, "tpu")
    assert plan.backend == backend and not plan.fuse_relabel
    forced = resolve_plan(n_vertices, n_edges, backend="pallas_blocked",
                          platform="tpu")
    assert forced.backend == "pallas_blocked" and not forced.interpret
    assert (forced.label_block, forced.chunk_updates) == (1024, 1024)


def test_pinned_plan_wins_over_cache(tmp_cache, monkeypatch):
    monkeypatch.setenv(cache.ENV_CACHE_PATH, tmp_cache)
    cached = ExecutionPlan(backend="xla", interpret=True, block_edges=64)
    cache.store(1000, 5000, "cpu", cached)
    pin = ExecutionPlan(backend="xla", interpret=True, block_edges=4096,
                        origin="pinned")
    got = resolve_plan(1000, 5000, backend="auto", plan=pin, platform="cpu")
    assert got.block_edges == 4096 and got.origin == "pinned"


def test_legacy_kernel_plan_is_lifted():
    legacy = mm_ops.KernelPlan(backend="xla", block_edges=128,
                               label_block=512, chunk_updates=32,
                               interpret=True)
    got = resolve_plan(10, 10, plan=legacy, platform="cpu")
    assert isinstance(got, ExecutionPlan)
    assert got.block_edges == 128 and got.label_block == 512
    assert got.origin == "pinned" and got.compact_schedule == "masked"


def test_auto_consults_cache_but_forced_backend_does_not(tmp_cache,
                                                         monkeypatch):
    monkeypatch.setenv(cache.ENV_CACHE_PATH, tmp_cache)
    tuned = ExecutionPlan(backend="xla", interpret=True, block_edges=99)
    cache.store(1000, 5000, "cpu", tuned)
    auto = resolve_plan(1000, 5000, backend="auto", platform="cpu")
    assert auto.block_edges == 99 and auto.origin == "tuned"
    forced = resolve_plan(1000, 5000, backend="xla", platform="cpu")
    assert forced.origin == "heuristic" and forced.block_edges != 99


def test_forced_pallas_off_tpu_gets_interpret_mode():
    p = resolve_plan(1000, 5000, backend="pallas_blocked", platform="cpu")
    assert p.backend == "pallas_blocked" and p.interpret
    t = resolve_plan(1000, 5000, backend="pallas_blocked", platform="tpu")
    assert not t.interpret


# --------------------------------------------------------------------- cache

def test_cache_round_trip_is_deterministic(tmp_cache):
    plan = heuristic_plan(5000, 200_000, "tpu").replace(origin="tuned")
    cache.store(5000, 200_000, "tpu", plan, time_s=0.5,
                timings={"a": 0.5}, path=tmp_cache)
    first = cache.lookup(5000, 200_000, "tpu", path=tmp_cache)
    second = cache.lookup(5000, 200_000, "tpu", path=tmp_cache)
    assert first is not None and first.config_equal(plan)
    assert first == second
    # buckets are pow2: a nearby size hits the same entry, a far one misses
    assert cache.lookup(5000, 200_001, "tpu", path=tmp_cache) is not None
    assert cache.lookup(5000, 500, "tpu", path=tmp_cache) is None
    assert cache.lookup(5000, 200_000, "cpu", path=tmp_cache) is None


@pytest.mark.parametrize("payload", [
    "not json at all {",
    json.dumps([1, 2, 3]),
    json.dumps({"schema": 999, "entries": {}}),
    json.dumps({"schema": 1, "entries": "nope"}),
])
def test_corrupt_cache_file_falls_back_without_crashing(tmp_cache, payload,
                                                        monkeypatch):
    with open(tmp_cache, "w") as f:
        f.write(payload)
    monkeypatch.setenv(cache.ENV_CACHE_PATH, tmp_cache)
    assert cache.lookup(1000, 5000, "cpu") is None
    got = resolve_plan(1000, 5000, backend="auto", platform="cpu")
    assert got.origin == "heuristic"


def test_corrupt_cache_entry_falls_back(tmp_cache):
    key = plan_key("cpu", 1000, 5000)
    for bad_entry in (
        "not a dict",
        {"origin": "tuned"},                       # no config at all
        {"origin": "tuned", "config": {"backend": "warp9"}},
        {"origin": "tuned", "config": {"backend": "xla", "mystery": 1}},
        {"origin": "tuned",
         "config": {"backend": "xla", "interpret": "yes"}},
        {"origin": "evil", "config": {"backend": "xla"}},
        {"origin": "fallback", "config": {"backend": "xla"}},  # no expiry
    ):
        with open(tmp_cache, "w") as f:
            json.dump({"schema": 1, "entries": {key: bad_entry}}, f)
        assert cache.lookup(1000, 5000, "cpu", path=tmp_cache) is None


def test_fallback_demotion_expires(tmp_cache):
    planner.record_kernel_failure(1000, 5000, "cpu",
                                  failed_backend="pallas_blocked",
                                  ttl_s=100.0, cache_path=tmp_cache)
    entry = cache.entries(tmp_cache)[plan_key("cpu", 1000, 5000)]
    live = cache.lookup(1000, 5000, "cpu", path=tmp_cache,
                        now=entry["measured_at"] + 50)
    assert live is not None and live.origin == "fallback"
    assert live.backend == "xla"
    expired = cache.lookup(1000, 5000, "cpu", path=tmp_cache,
                           now=entry["measured_at"] + 101)
    assert expired is None  # lapsed: the bucket retunes, XLA is not pinned


def test_cache_clear(tmp_cache):
    plan = ExecutionPlan(backend="xla", interpret=True)
    cache.store(10, 10, "cpu", plan, path=tmp_cache)
    assert cache.entries(tmp_cache)
    cache.clear(tmp_cache)
    assert not cache.entries(tmp_cache)
    assert cache.lookup(10, 10, "cpu", path=tmp_cache) is None


# ---------------------------------------------------------------------- vmem

def test_vmem_ceiling_boundary():
    # default budget (16 MiB): 3/4 of it for L, 4 bytes per label
    assert planner.whole_l_vmem_ceiling("tpu") == 3_145_728
    assert mm_ops.WHOLE_L_VMEM_CEILING == planner.whole_l_vmem_ceiling()
    # exact boundary arithmetic on a toy budget
    assert planner.whole_l_vmem_ceiling("tpu", vmem_bytes=16) == 3
    assert planner.vmem_budget_bytes("tpu", override=1234) == 1234
    with pytest.raises(ValueError):
        planner.vmem_budget_bytes("tpu", override=0)


def test_vmem_env_override(monkeypatch):
    monkeypatch.setenv(planner.ENV_VMEM_BYTES, "32")
    assert planner.vmem_budget_bytes("cpu") == 32
    assert planner.whole_l_vmem_ceiling("cpu") == 6
    monkeypatch.setenv(planner.ENV_VMEM_BYTES, "banana")
    with pytest.raises(ValueError, match="REPRO_VMEM_BYTES"):
        planner.vmem_budget_bytes("cpu")


def test_scalar_pallas_ceiling_uses_solve_options_override():
    g = gen.path(64, seed=0)
    # a 16-byte budget allows 3 whole-L labels: n=64 must refuse clearly
    with pytest.raises(ValueError, match="ceiling"):
        solve(g, backend="pallas", vmem_limit_bytes=16)
    # raising the budget over 4*n/0.75 bytes admits the same graph
    res = solve(g, backend="pallas", vmem_limit_bytes=1 << 20)
    oracle = connected_components_oracle(*g.to_numpy())
    assert labels_equivalent(np.asarray(res.labels), oracle)


def test_scalar_pallas_ceiling_env(monkeypatch):
    g = gen.path(64, seed=0)
    monkeypatch.setenv(planner.ENV_VMEM_BYTES, "16")
    with pytest.raises(ValueError, match="ceiling"):
        solve(g, backend="pallas")


# --------------------------------------------------------- deprecation shim

def test_plan_contour_kernel_is_a_warning_shim():
    with pytest.warns(DeprecationWarning, match="plan_contour_kernel"):
        legacy = mm_ops.plan_contour_kernel(1000, 5000)
    rich = heuristic_plan(1000, 5000)
    assert isinstance(legacy, mm_ops.KernelPlan)
    assert legacy.backend == rich.backend
    assert legacy.label_block == rich.label_block
    assert legacy.interpret == rich.interpret


# ------------------------------------------------- schedule / fused kernels

@pytest.mark.parametrize("n,m,seed", [(200, 900, 0), (500, 3000, 1),
                                      (257, 1100, 2)])
@pytest.mark.parametrize("sampling,compact_every", [(0, 2), (2, 2), (2, 0)])
def test_staged_masked_dense_oracle_bit_exact(n, m, seed, sampling,
                                              compact_every):
    rng = np.random.default_rng(seed)
    src = jnp.asarray(rng.integers(0, n, m), jnp.int32)
    dst = jnp.asarray(rng.integers(0, n, m), jnp.int32)
    dense = contour_labels(src, dst, n, variant="C-2")[0]
    masked = contour_labels(src, dst, n, variant="C-2", sampling=sampling,
                            compact_every=compact_every)
    staged = staged_mod.staged_adaptive_labels(
        src, dst, n, variant="C-2", sampling=sampling,
        compact_every=compact_every)
    oracle = connected_components_oracle(np.asarray(src), np.asarray(dst), n)
    assert np.array_equal(np.asarray(masked[0]), np.asarray(dense))
    assert np.array_equal(np.asarray(staged[0]), np.asarray(dense))
    assert int(staged[1]) == int(masked[1])          # iteration counts
    assert float(staged[3]) == float(masked[3])      # visited counters
    assert labels_equivalent(np.asarray(staged[0]), oracle)


def test_staged_rejects_csyn_and_negative_schedule():
    g = gen.path(100, seed=0)
    with pytest.raises(ValueError, match="C-Syn"):
        staged_mod.staged_adaptive_labels(g.src, g.dst, g.n_vertices,
                                          variant="C-Syn", sampling=2)
    with pytest.raises(ValueError, match=">= 0"):
        staged_mod.staged_adaptive_labels(g.src, g.dst, g.n_vertices,
                                          sampling=-1)


@pytest.mark.slow
@pytest.mark.parametrize("n,m,seed", [(100, 300, 0), (300, 1500, 1)])
def test_fused_relax_bit_exact_with_reference(n, m, seed):
    from repro.kernels.contour_mm.blocked import fused_relax_pallas
    from repro.connectivity import minmap as lab
    rng = np.random.default_rng(seed)
    src = jnp.asarray(rng.integers(0, n, m), jnp.int32)
    dst = jnp.asarray(rng.integers(0, n, m), jnp.int32)
    L = jnp.minimum(jnp.arange(n, dtype=jnp.int32),
                    jnp.asarray(rng.integers(0, n, n), jnp.int32))
    L = L.at[0].set(0)
    ref = lab.mm_relax(L, src, dst, 2)
    fused = fused_relax_pallas(L, src, dst, chunk_edges=64, interpret=True)
    assert np.array_equal(np.asarray(fused), np.asarray(ref))
    # the frontier-limited form: suffix edges must not contribute
    limit = jnp.int32(m // 3)
    ref_lim = lab.mm_relax(L, jnp.where(jnp.arange(m) < limit, src, 0),
                           jnp.where(jnp.arange(m) < limit, dst, 0), 2)
    fused_lim = fused_relax_pallas(L, src, dst, chunk_edges=64,
                                   interpret=True, edge_limit=limit)
    assert np.array_equal(np.asarray(fused_lim), np.asarray(ref_lim))


@pytest.mark.slow
def test_fused_plan_routes_through_dispatch(graph):
    """A single-tile fused plan and the unfused path agree elementwise."""
    plan = heuristic_plan(graph.n_vertices, graph.n_edges, "tpu")
    assert plan.fuse_relabel  # small graph: single-tile fused regime
    fused = solve(graph, backend="pallas_blocked",
                  plan=plan.replace(interpret=True))
    unfused = solve(graph, backend="pallas_blocked",
                    plan=plan.replace(interpret=True, fuse_relabel=False))
    assert np.array_equal(np.asarray(fused.labels),
                          np.asarray(unfused.labels))
    assert "fused=1" in fused.provenance[0]
    assert "fused=0" in unfused.provenance[0]


# ----------------------------------------------------------------- autotune

@pytest.mark.tuning
def test_autotune_deterministic_with_injected_measure(graph, tmp_cache):
    # fake clock: the staged-schedule candidate is 2x faster
    def measure(g, plan, opts):
        return 0.05 if plan.compact_schedule == "staged" else 0.10

    tuned, timings = planner.autotune(graph, platform="cpu", measure=measure,
                                      cache_path=tmp_cache)
    assert tuned.origin == "tuned"
    assert tuned.compact_schedule == "staged"
    assert len(timings) >= 2
    # round-trips through the cache: the next auto resolution deploys it
    again = cache.lookup(graph.n_vertices, graph.n_edges, "cpu",
                         path=tmp_cache)
    assert again is not None and again.config_equal(tuned)


@pytest.mark.tuning
def test_autotune_hysteresis_keeps_prior_on_near_tie(graph, tmp_cache):
    heur = heuristic_plan(graph.n_vertices, graph.n_edges, "cpu")

    def measure(g, plan, opts):  # alternative is only 2% faster
        return 0.098 if not plan.config_equal(heur) else 0.10

    tuned, _ = planner.autotune(graph, platform="cpu", measure=measure,
                                cache_path=tmp_cache, margin=0.05)
    assert tuned.config_equal(heur)


@pytest.mark.tuning
@pytest.mark.slow
def test_autotuned_plans_bit_exact_across_backends(graph, tmp_cache):
    """Tuning changes wall time, never labels — on every backend."""
    oracle = connected_components_oracle(*graph.to_numpy())
    heur_cpu = heuristic_plan(graph.n_vertices, graph.n_edges, "cpu")
    reference = solve(graph, options=SolveOptions(
        sampling=2, compact_every=2, plan=heur_cpu))

    def measure(g, plan, opts):  # force a non-prior winner deterministically
        return 0.01 if plan.compact_schedule != \
            heur_cpu.compact_schedule else 1.0

    tuned, _ = planner.autotune(graph, platform="cpu", measure=measure,
                                cache_path=tmp_cache)
    assert not tuned.config_equal(heur_cpu)
    for plan in (
        tuned,
        heur_cpu,
        heuristic_plan(graph.n_vertices, graph.n_edges, "tpu")
        .replace(backend="pallas_blocked", interpret=True),
    ):
        res = solve(graph, options=SolveOptions(
            backend=plan.backend, sampling=2, compact_every=2, plan=plan))
        assert np.array_equal(np.asarray(res.labels),
                              np.asarray(reference.labels)), plan
        assert labels_equivalent(np.asarray(res.labels), oracle)


# --------------------------------------------------------------- provenance

def test_one_shot_solve_records_plan(graph, monkeypatch, tmp_cache):
    # fresh cache: demotions left by other tests must not shadow the tables
    monkeypatch.setenv(cache.ENV_CACHE_PATH, tmp_cache)
    res = solve(graph)
    assert res.provenance is not None
    assert res.provenance[0].startswith("plan:")
    assert "origin=heuristic" in res.provenance[0]
    forced = solve(graph, backend="xla")
    assert forced.provenance[0].startswith("plan:xla")


def test_pinned_plan_provenance(graph):
    pin = ExecutionPlan(backend="xla", interpret=True, origin="pinned")
    res = solve(graph, options=SolveOptions(backend="xla", plan=pin))
    assert "origin=pinned" in res.provenance[0]


def test_cached_plan_provenance(graph, monkeypatch, tmp_cache):
    monkeypatch.setenv(cache.ENV_CACHE_PATH, tmp_cache)
    tuned = heuristic_plan(graph.n_vertices, graph.n_edges,
                           "cpu").replace(origin="tuned")
    cache.store(graph.n_vertices, graph.n_edges, "cpu", tuned,
                path=tmp_cache)
    res = solve(graph)     # backend="auto" consults the cache
    assert "origin=tuned" in res.provenance[0]


def test_batch_solve_records_plan(graph, monkeypatch, tmp_cache):
    monkeypatch.setenv(cache.ENV_CACHE_PATH, tmp_cache)
    res = solve_batch([graph, graph])
    assert res.provenance is not None
    assert res.provenance[0].startswith("plan:")


def test_unplanned_solvers_record_no_plan(graph):
    assert solve(graph, algorithm="fastsv").provenance is None
    assert solve(graph, algorithm="union_find").provenance is None


# ------------------------------------------------------- bench-layer pieces

def test_validate_backend_rejects_unknown():
    from benchmarks.connectivity import validate_backend
    with pytest.raises(SystemExit, match="unknown backend"):
        validate_backend("warp9")
    validate_backend("auto")   # no probe, no error
    validate_backend("xla")


def test_check_artifact_schema5_rederives_from_raw_timings():
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "check_artifact", os.path.join(os.path.dirname(__file__), "..",
                                       "benchmarks", "check_artifact.py"))
    ca = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(ca)
    good = {
        "schema": 5,
        "frontier_wallclock_gate": {
            "g1": {"dense_s": 1.0, "masked_s": 1.5, "staged_s": 0.5},
        },
        "autotune_gate": {
            "g1": {"plan_differs": False, "ratio": 1.0},
            "g2": {"plan_differs": True, "heuristic_s": 1.2, "tuned_s": 1.0},
        },
    }
    assert ca.check_wallclock_gates(good) == []
    slow = json.loads(json.dumps(good))
    slow["frontier_wallclock_gate"]["g1"]["staged_s"] = 2.0
    assert any("no schedule beats dense" in e
               for e in ca.check_wallclock_gates(slow))
    regress = json.loads(json.dumps(good))
    regress["autotune_gate"]["g2"].update(heuristic_s=1.0, tuned_s=1.3)
    assert any("geomean" in e for e in ca.check_wallclock_gates(regress))
    missing = {"schema": 5}
    errs = ca.check_wallclock_gates(missing)
    assert len(errs) == 2  # both gates reported missing
    # a summary edited to look healthy cannot mask failing raw timings
    slow["summary"] = {"frontier_beats_dense_wallclock": True}
    assert ca.check(dict(slow, summary={
        "all_correct": True, "frontier_beats_dense_wallclock": True}))
