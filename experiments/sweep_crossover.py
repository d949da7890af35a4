#!/usr/bin/env python3
"""Time the MM sweep's two realisations above the single-tile regime.

    python experiments/sweep_crossover.py [--seed N] [--reps K] [--only a,b]
    JAX_PLATFORMS=cpu python experiments/sweep_crossover.py --tiny

On one TPU chip, for each shape below, ``solve(graph, backend=b)`` (or a
warm-started ``StreamingConnectivity`` ingesting batches) runs under
``b = "xla"`` and ``b = "pallas_blocked"``, each forced, with the kernel
fallback off.  Solves are timed cold (compile included) and then ``--reps``
times warm, each until the labels are ready; ingests are timed batch by
batch after warm-up batches.  Both backends must reach identical labels
and the same sweep count.  These timings, with the benchmark's stream
cell (whose longer window ran a batch slower on XLA), set the planner's
TPU table (``planner/heuristics.py::heuristic_plan``).

Shapes (graphs drawn by the benchmark's generators, from ``--seed``):

* ``graph500-s20``: Graph500 Kronecker, n = 2^20, m = 2^24;
* ``delaunay-n20``: Delaunay triangulation of 2^20 points, m = 3,145,688;
* ``stream``: half of graph500-s20 as the warm start, then 2^16-edge
  batches into n = 2^20 labels (2^18 updates a sweep);
* ``graph500-s22``: Graph500 Kronecker, n = 2^22, m = 2^26.

One JSON line per shape and backend on stdout.  ``--tiny`` runs every
shape at a few thousand vertices on any platform (Pallas then
interprets): a rehearsal of the script, not a measurement.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BACKENDS = ("xla", "pallas_blocked")
SHAPES = ("graph500-s20", "stream", "delaunay-n20", "graph500-s22")
STREAM_BATCH = 1 << 16


def log(msg: str) -> None:
    print(f"sweep_crossover: {msg}", file=sys.stderr, flush=True)


def configure_jax():
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(ROOT, ".jax_cache"))
    return jax


def config(name: str, tiny: bool) -> dict:
    with open(os.path.join(ROOT, "bench", "configs", name + ".json")) as f:
        cfg = json.load(f)
    if tiny:
        cfg["scale"] = 12
    return cfg


def plan_of(res) -> str:
    prov = tuple(res.provenance or ())
    bad = [p for p in prov
           if p.startswith("kernel_fallback:") or "origin=fallback" in p]
    if bad:
        raise RuntimeError(f"fell back off the forced backend: {bad}")
    return [p for p in prov if p.startswith("plan:")][-1]


def time_solves(graph, backend: str, reps: int) -> tuple:
    import repro

    def one():
        t0 = time.perf_counter()
        res = repro.solve(graph, backend=backend, kernel_fallback=False)
        res.labels.block_until_ready()
        return res, time.perf_counter() - t0

    res, cold = one()
    warm = [one()[1] for _ in range(reps)]
    row = {"backend": backend, "plan": plan_of(res),
           "iterations": int(res.iterations), "cold_s": cold,
           "warm_s": warm, "warm_min_s": min(warm)}
    return row, np.asarray(res.labels)


def solve_shape(name: str, draw, reps: int) -> list:
    import repro

    src, dst, n = draw()
    graph = repro.Graph(src=src, dst=dst, n_vertices=n)
    rows, labels = [], []
    for backend in BACKENDS:
        row, lab = time_solves(graph, backend, reps)
        row.update(shape=name, n=n, m=int(src.shape[0]))
        rows.append(row)
        labels.append(lab)
        log(json.dumps(row))
    same = bool(np.array_equal(labels[0], labels[1])
                and rows[0]["iterations"] == rows[1]["iterations"])
    for row in rows:
        row["same_labels_and_sweeps"] = same
    return rows


def stream_shape(cfg: dict, seed: int, reps: int, tiny: bool) -> list:
    import repro
    from bench.batches import kronecker as batches
    from bench.generators import kronecker

    src, dst, n = kronecker.draw(cfg, seed)
    half = int(src.shape[0]) // 2
    k = 256 if tiny else STREAM_BATCH
    warmup, timed = 16, 16 * max(reps, 1)
    pool = batches.Batches(cfg, seed, k, warmup + timed).chunk(0)
    rows, labels = [], []
    for backend in BACKENDS:
        base = repro.solve(repro.Graph(src=src[:half], dst=dst[:half],
                                       n_vertices=n),
                           backend=backend, kernel_fallback=False)
        eng = repro.StreamingConnectivity(n, warm_start=base,
                                          backend=backend,
                                          kernel_fallback=False)
        # the edge store at its final size, so no growth compiles later
        eng._ensure_capacity(len(pool) * k)
        ms = []
        for i, (s, d) in enumerate(pool):
            t0 = time.perf_counter()
            eng.ingest(s, d)
            eng.labels.block_until_ready()
            if i >= warmup:
                ms.append((time.perf_counter() - t0) * 1e3)
        snap = eng.snapshot()
        q = np.percentile(ms, [50, 95])
        row = {"shape": "stream", "backend": backend, "plan": plan_of(snap),
               "n": n, "batch_edges": k, "batches_timed": len(ms),
               "batch_ms_mean": float(np.mean(ms)),
               "batch_ms_p50": float(q[0]), "batch_ms_p95": float(q[1]),
               "sweeps_per_batch": (int(snap.iterations)
                                    - int(base.iterations)) / len(pool)}
        rows.append(row)
        labels.append(np.asarray(snap.labels))
        log(json.dumps(row))
        del eng, snap, base
    same = bool(np.array_equal(labels[0], labels[1]))
    for row in rows:
        row["same_labels_and_sweeps"] = same
    return rows


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=14)
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--only", default=",".join(SHAPES))
    ap.add_argument("--tiny", action="store_true")
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    jax = configure_jax()
    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.tiny:
        log(f"needs a TPU; JAX found {dev.platform}")
        return 1
    from bench.generators import delaunay, kronecker

    g500 = config("graph500-s20", args.tiny)
    g22 = dict(g500, scale=g500["scale"] + 2)
    dln = config("delaunay-n20", args.tiny)
    rows = []
    for shape in args.only.split(","):
        log(f"{shape}: start")
        if shape == "graph500-s20":
            rows += solve_shape(shape, lambda: kronecker.draw(g500, args.seed),
                                args.reps)
        elif shape == "graph500-s22":
            rows += solve_shape(shape, lambda: kronecker.draw(g22, args.seed),
                                1)
        elif shape == "delaunay-n20":
            rows += solve_shape(shape, lambda: delaunay.draw(dln, args.seed),
                                args.reps)
        elif shape == "stream":
            rows += stream_shape(g500, args.seed, args.reps, args.tiny)
        else:
            raise SystemExit(f"unknown shape {shape!r}; one of {SHAPES}")
    for row in rows:
        row["device_kind"] = dev.device_kind
        print(json.dumps(row), flush=True)
    return 0 if all(r["same_labels_and_sweeps"] for r in rows) else 1


if __name__ == "__main__":
    sys.exit(main())
