"""Benchmark entry: ``python -m benchmarks.run [--fast]``.

One section per paper table/figure plus the production-integration and
roofline reports:

  fig1  iterations per method              (paper Fig. 1)
  fig2  execution time                     (paper Fig. 2)
  fig3  speedup vs FastSV                  (paper Fig. 3)
  fig4  speedup vs ConnectIt               (paper Fig. 4)
  scale Delaunay scaling trend             (paper §IV-D)
  dist  distributed shard_map contour      (paper §IV-G analogue)
  dedup MinHash+Contour dedup integration
  ooc   out-of-core contraction gate       (DESIGN.md §15)
  roof  dry-run roofline tables            (EXPERIMENTS.md §Roofline)
  serve serving-engine traffic + recovery  (DESIGN.md §13)

After the sections run, the connectivity suite records (per-method wall
time + iteration counts, including the ``C-2-blk`` kernel path) are
written to ``BENCH_connectivity.json`` so the perf trajectory stays
machine-readable across PRs; disable with ``--json ''``.
"""
from __future__ import annotations

import argparse
import json
import os
import time
import traceback

import jax

from benchmarks import (
    connectivity,
    dedup_bench,
    distributed_scaling,
    fig1_iterations,
    fig2_time,
    fig3_speedup_fastsv,
    fig4_speedup_connectit,
    oocore,
    recovery,
    roofline_report,
    scaling_delaunay,
    serving,
    streaming,
)

SECTIONS = [
    ("fig1_iterations", fig1_iterations.main),
    ("fig2_time", fig2_time.main),
    ("fig3_speedup_vs_fastsv", fig3_speedup_fastsv.main),
    ("fig4_speedup_vs_connectit", fig4_speedup_connectit.main),
    ("delaunay_scaling", scaling_delaunay.main),
    ("distributed_contour", distributed_scaling.main),
    ("dedup_integration", dedup_bench.main),
    ("streaming_vs_scratch", streaming.main),
    ("oocore_gate", oocore.main),
    ("recovery_overhead", recovery.main),
    ("roofline_report", roofline_report.main),
    # writes BENCH_serving.json itself (traffic SLO + recovery gate)
    ("serving_engine", serving.main),
]

# JAX's persistent compile cache when JAX_COMPILATION_CACHE_DIR is unset
# (when it is set, JAX reads it itself): one fixed directory in the
# checkout, so a later run finds what an earlier one compiled.
CACHE_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), ".jax_cache")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true",
                    help="subsampled suite for quick runs")
    ap.add_argument("--only", help="comma-separated section prefixes "
                                   "('bench' = artifact-only regen)")
    ap.add_argument("--json", default="BENCH_connectivity.json",
                    help="connectivity artifact path ('' disables)")
    ap.add_argument("--backend", default="auto",
                    help="kernel backend for the suite (validated up "
                         "front: a backend that cannot compile on this "
                         "host fails fast with a clear error)")
    ap.add_argument("--retune", action="store_true",
                    help="clear the plan tuning cache and re-run the "
                         "measuring autotuner from scratch")
    ap.add_argument("--strategy", default=None,
                    help="comma-separated sampling strategies (or 'auto') "
                         "to restrict the strategy-matrix gate to; "
                         "default: all registered strategies + auto")
    args = ap.parse_args()
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", CACHE_DIR)

    # Fail fast on an impossible backend request *before* any section
    # runs — a raw Pallas lowering error mid-suite helps nobody.
    connectivity.validate_backend(args.backend)
    if args.strategy is not None:
        from repro.connectivity.frontier import SAMPLING_STRATEGIES
        known = tuple(SAMPLING_STRATEGIES) + ("auto",)
        requested = tuple(s for s in args.strategy.split(",") if s)
        for s in requested:
            if s not in known:
                raise SystemExit(
                    f"unknown strategy {s!r}: choose from {known}\n"
                    "hint: strategies are registered in "
                    "repro.connectivity.frontier "
                    "(register_sampling_strategy); 'auto' is the cost-"
                    "model dispatch, not a sampling strategy name")
        connectivity.set_strategy_sides(requested)
    if args.backend != "auto":
        connectivity.set_backend(args.backend)

    failures = []
    for name, fn in SECTIONS:
        if args.only and not any(name.startswith(p)
                                 for p in args.only.split(",")):
            continue
        print(f"\n{'=' * 72}\n[{name}]\n{'=' * 72}")
        t0 = time.time()
        try:
            fn(fast=args.fast)
            print(f"[{name}] done in {time.time() - t0:.1f}s")
        except Exception:  # noqa: BLE001 — report all sections
            failures.append(name)
            traceback.print_exc()
    # Emit the artifact when the connectivity suite is in play (no --only,
    # a fig section selected — then run_suite() is already cached — or
    # the explicit 'bench' pseudo-section for artifact-only regen);
    # `--only roof --json x` should not trigger a full suite run.
    want_json = args.json and (
        not args.only
        or any(p.startswith(("fig", "bench"))
               for p in args.only.split(",")))
    if want_json:
        try:
            records = connectivity.run_suite(fast=args.fast)
            gate = connectivity.blocked_vs_xla_gate(fast=args.fast)
            stream_gate = streaming.run_gate(fast=args.fast)
            fw_gate = connectivity.frontier_wallclock_gate(fast=args.fast)
            tune_gate = connectivity.autotune_gate(fast=args.fast,
                                                   retune=args.retune)
            oo_gate = oocore.run_gate(fast=args.fast)
            strat_gate = connectivity.strategy_matrix_gate(fast=args.fast)
            from repro.connectivity import planner as _planner
            payload = connectivity.records_to_json(
                records, fast=args.fast, gate=gate, streaming=stream_gate,
                frontier_wallclock=fw_gate, autotune=tune_gate,
                tuning_cache=_planner.cache.entries(),
                oocore=oo_gate, strategy=strat_gate)
            recovery.merge_into_artifact(payload,
                                         recovery.run_gate(fast=args.fast))
            with open(args.json, "w") as f:
                json.dump(payload, f, indent=2)
            print(f"\nwrote {args.json}: {payload['summary']}")
        except Exception:  # noqa: BLE001 — keep the failure report intact
            failures.append("bench_json")
            traceback.print_exc()
    if failures:
        raise SystemExit(f"benchmark sections failed: {failures}")
    print("\nall benchmark sections passed")


if __name__ == "__main__":
    main()
