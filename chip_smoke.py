#!/usr/bin/env python3
"""Run the connectivity system's main path once on a TPU and check it.

    python chip_smoke.py              # one chip: phases A-D below
    python chip_smoke.py --chips 4    # four chips: the sharded solve only

One chip (the default):

  A. Graph500 Kronecker graph, scale 22, edge factor 16, A/B/C =
     0.57/0.19/0.19 (the LDBC Graphalytics graph500-22 shape), solved by
     ``solve()`` with the kernel fallback off, timed cold (compile
     included) and warm.  Labels must equal scipy's connected components
     relabelled to the minimum vertex id, and the provenance must show no
     fallback.
  B. The same graph on the label-blocked Pallas kernel, forced
     (``backend="pallas_blocked"``; the planner picks XLA at this size);
     labels must equal A's.
  C. A single-tile graph (scale 12, n = 4096), where the planner picks the
     fused relabel + scatter-min Pallas pass.
  D. ``StreamingConnectivity`` ingests A's edges in 8 batches; its
     snapshot must equal A's labels.

Four chips (``--chips 4``): a Graph500 Kronecker edge list, scale 24,
edge factor 16, generated on the chips with each chip drawing its own
quarter (duplicates and self-loops kept, as the Graph500 generator emits
them), solved through ``solve(..., mesh=...)`` over a ``("data",)`` mesh
of the four local chips and checked against scipy.  Each chip's shard is
as large as the one-chip phase A edge list.  It prints which chip holds
which edge shard, the rounds, the solve's seconds (compile included) and
every chip's peak bytes.

Each phase prints one JSON line.  The last line is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
The script exits non-zero, printing no such line, when JAX finds no TPU,
when the repository's ``src/`` is missing, or when any phase fails.

JAX's persistent compile cache lives where ``JAX_COMPILATION_CACHE_DIR``
says; when it is unset, the script keeps it in ``.jax_cache/`` at the
root of the checkout.
"""
from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time
import traceback

import numpy as np

# Graph500 scales of the phases (vertices = 2**scale, edge factor 16).
SCALE_ONE_CHIP = 22
SCALE_SINGLE_TILE = 12
SCALE_FOUR_CHIPS = 24
EDGE_FACTOR = 16

REPO = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(REPO, "src")
CACHE_DIR = os.path.join(REPO, ".jax_cache")


class SmokeFailure(Exception):
    """A phase produced a wrong or unexpected result."""


def emit(**row) -> None:
    print(json.dumps(row), flush=True)


def log(msg: str) -> None:
    """Progress on stderr, with the host's peak resident memory."""
    import resource
    peak = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 2**20
    print(f"chip_smoke: {msg} (host peak RSS {peak:.1f} GiB)",
          file=sys.stderr, flush=True)


def reference_labels(src: np.ndarray, dst: np.ndarray, n: int) -> np.ndarray:
    """scipy connected components, each labelled by its minimum vertex id."""
    import scipy.sparse as sp
    from scipy.sparse.csgraph import connected_components

    # bool entries: duplicate edges OR together instead of summing
    adj = sp.coo_matrix((np.ones(len(src), bool), (src, dst)),
                        shape=(n, n)).tocsr()
    _, comp = connected_components(adj, directed=False)
    # vertices are visited in id order, so a component's first index is
    # its minimum id
    _, first = np.unique(comp, return_index=True)
    return first[comp].astype(np.int32)


def timed(fn):
    """(result, seconds) of ``fn()``, ending once its labels are ready."""
    t0 = time.perf_counter()
    res = fn()
    res.labels.block_until_ready()
    return res, time.perf_counter() - t0


def plan_of(res) -> str:
    """The result's ``plan:`` entry; fails on any fallback entry."""
    prov = tuple(res.provenance or ())
    bad = [p for p in prov
           if p.startswith("kernel_fallback:") or "origin=fallback" in p]
    if bad:
        raise SmokeFailure(f"solve fell back: {bad}")
    plans = [p for p in prov if p.startswith("plan:")]
    if not plans:
        raise SmokeFailure(f"no plan in provenance {prov}")
    return plans[-1]


def check_equal(name: str, got, want: np.ndarray) -> None:
    got = np.asarray(got)
    if got.shape != want.shape or not np.array_equal(got, want):
        bad = int(np.sum(got != want)) if got.shape == want.shape else -1
        raise SmokeFailure(f"{name}: labels differ from the reference at "
                           f"{bad} vertices")


def peak_bytes(device) -> int | None:
    stats = device.memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def one_chip(seed: int, device) -> None:
    from repro import SolveOptions, solve
    from repro.connectivity import StreamingConnectivity
    from repro.graphs import generators as gen

    opts = SolveOptions(kernel_fallback=False)

    # -- A: Graph500 scale 22 through solve() ------------------------------
    t0 = time.perf_counter()
    g = gen.rmat(SCALE_ONE_CHIP, edge_factor=EDGE_FACTOR, seed=seed)
    src, dst, n = g.to_numpy()
    gen_s = time.perf_counter() - t0
    log(f"A: generated graph500-{SCALE_ONE_CHIP} in {gen_s:.1f} s")
    t0 = time.perf_counter()
    ref = reference_labels(src, dst, n)
    ref_s = time.perf_counter() - t0
    log(f"A: scipy reference in {ref_s:.1f} s")
    res, cold = timed(lambda: solve(g, opts))
    log(f"A: first solve in {cold:.1f} s")
    res, warm = timed(lambda: solve(g, opts))
    plan = plan_of(res)
    labels_a = np.asarray(res.labels)
    check_equal("A solve", labels_a, ref)
    emit(phase="A", graph=f"graph500-{SCALE_ONE_CHIP}", n=n, m=g.n_edges,
         plan=plan, compile_plus_run_s=cold, warm_s=warm,
         iterations=int(res.iterations), converged=bool(res.converged),
         n_components=int(np.sum(labels_a == np.arange(n))),
         generate_s=gen_s, reference_s=ref_s,
         peak_bytes_in_use=peak_bytes(device),
         device_kind=device.device_kind)

    # -- B: the blocked Pallas kernel, forced, on the same graph ------------
    log("B: start")
    res_x, cold = timed(lambda: solve(g, opts, backend="pallas_blocked"))
    res_x, warm = timed(lambda: solve(g, opts, backend="pallas_blocked"))
    check_equal("B pallas_blocked", res_x.labels, labels_a)
    emit(phase="B", plan=plan_of(res_x), compile_plus_run_s=cold,
         warm_s=warm, iterations=int(res_x.iterations),
         peak_bytes_in_use=peak_bytes(device))

    # -- C: single tile, the fused Pallas pass ------------------------------
    log("C: start")
    small = gen.rmat(SCALE_SINGLE_TILE, edge_factor=EDGE_FACTOR, seed=seed)
    res_c, cold = timed(lambda: solve(small, opts))
    res_c, warm = timed(lambda: solve(small, opts))
    plan_c = plan_of(res_c)
    if not (plan_c.startswith("plan:pallas_blocked") and "fused=1" in plan_c):
        raise SmokeFailure(f"C did not run the fused kernel: {plan_c}")
    check_equal("C fused", res_c.labels, reference_labels(*small.to_numpy()))
    emit(phase="C", graph=f"graph500-{SCALE_SINGLE_TILE}",
         n=small.n_vertices, m=small.n_edges, plan=plan_c,
         compile_plus_run_s=cold,
         warm_s=warm, iterations=int(res_c.iterations))

    # -- D: the streaming engine, 8 batches of A's edges --------------------
    log("D: start")
    del g, res, res_x
    eng = StreamingConnectivity(n_vertices=n, options=opts)
    bounds = np.linspace(0, len(src), 9).astype(np.int64)
    t0 = time.perf_counter()
    for lo, hi in zip(bounds[:-1], bounds[1:]):
        eng.ingest(src[lo:hi], dst[lo:hi])
    snap = eng.snapshot()
    snap.labels.block_until_ready()
    ingest_s = time.perf_counter() - t0
    check_equal("D streaming", snap.labels, labels_a)
    emit(phase="D", batches=8, plan=plan_of(snap),
         ingest_all_s=ingest_s, iterations=int(snap.iterations),
         peak_bytes_in_use=peak_bytes(device))


def kronecker_edges(scale: int, seed: int, sharding):
    """Graph500 Kronecker edges (A/B/C = 0.57/0.19/0.19), drawn on device.

    The jitted generator's outputs carry ``sharding``, so with a sharded
    layout each device draws only its own slice of the edge list.
    """
    import jax
    import jax.numpy as jnp

    n = 1 << scale
    m = n * EDGE_FACTOR
    ab = 0.57 + 0.19
    a_norm, c_norm = 0.57 / ab, 0.19 / (1.0 - ab)

    @functools.partial(jax.jit, out_shardings=(sharding, sharding))
    def make(key, perm):
        def one_bit(bit, edges):
            src, dst = edges
            k_row, k_col = jax.random.split(jax.random.fold_in(key, bit))
            row = jax.random.uniform(k_row, (m,)) > ab
            p_col = jnp.where(row, c_norm, a_norm)
            col = jax.random.uniform(k_col, (m,)) > p_col
            return (src | (row.astype(jnp.int32) << bit),
                    dst | (col.astype(jnp.int32) << bit))

        zeros = jax.lax.with_sharding_constraint(
            jnp.zeros((m,), jnp.int32), sharding)
        src, dst = jax.lax.fori_loop(0, scale, one_bit, (zeros, zeros))
        return perm[src], perm[dst]

    # permute ids so degree isn't correlated with vertex id (on the host:
    # a device permutation of 2**24 ids takes most of a minute to compile)
    perm = np.random.default_rng(seed).permutation(n).astype(np.int32)
    return make(jax.random.key(seed), perm)


def four_chips(seed: int) -> None:
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro import Graph, SolveOptions, jax_compat, solve

    if len(jax.devices()) < 4:
        raise SmokeFailure(f"--chips 4 needs 4 devices, JAX found "
                           f"{len(jax.devices())}")
    mesh = jax_compat.make_mesh((4,), ("data",))
    n = 1 << SCALE_FOUR_CHIPS
    t0 = time.perf_counter()
    src_d, dst_d = kronecker_edges(SCALE_FOUR_CHIPS, seed,
                                   NamedSharding(mesh, P("data")))
    src_d.block_until_ready()
    gen_s = time.perf_counter() - t0
    shards = sorted((s.device.id, s.data.shape[0])
                    for s in src_d.addressable_shards)
    if len({d for d, _ in shards}) != 4:
        raise SmokeFailure(f"edge shards are not on 4 chips: {shards}")
    log(f"generated graph500-{SCALE_FOUR_CHIPS} on the chips in "
        f"{gen_s:.1f} s; shards {shards}")
    t0 = time.perf_counter()
    src, dst = np.asarray(src_d), np.asarray(dst_d)
    ref = reference_labels(src, dst, n)
    ref_s = time.perf_counter() - t0
    log(f"scipy reference in {ref_s:.1f} s")

    g = Graph(src=src_d, dst=dst_d, n_vertices=n)
    opts = SolveOptions(mesh=mesh, kernel_fallback=False)
    # one solve, compile included: chip time on four chips costs four
    # times as much, and the one-chip phases already time warm solves
    res, cold = timed(lambda: solve(g, opts))
    log(f"sharded solve in {cold:.1f} s")
    plan = plan_of(res)
    labels = np.asarray(res.labels)
    check_equal("4-chip solve", labels, ref)
    peaks = [peak_bytes(d) for d in mesh.devices.flat]
    # each chip ran its own shard's sweep: no chip's peak is a small
    # fraction of the busiest one's
    if None not in peaks and min(peaks) < max(peaks) // 2:
        raise SmokeFailure(f"uneven per-chip peaks {peaks}: shards did "
                           "not run on their own chips")
    emit(phase="4chip", graph=f"graph500-{SCALE_FOUR_CHIPS}", n=n,
         m=len(src), plan=plan,
         edge_shards=[{"device": d, "edges": k} for d, k in shards],
         compile_plus_run_s=cold,
         rounds=int(res.iterations), converged=bool(res.converged),
         n_components=int(np.sum(labels == np.arange(n))),
         generate_s=gen_s, reference_s=ref_s,
         peak_bytes_in_use_per_chip=peaks,
         device_kind=mesh.devices.flat[0].device_kind)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    if not os.path.isdir(os.path.join(SRC, "repro")):
        print(f"chip_smoke: no repro package under {SRC}; run from a "
              "checkout of the repository", file=sys.stderr)
        return 2
    sys.path.insert(0, SRC)
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    devices = jax.devices()
    platform = devices[0].platform
    log(f"JAX sees {len(devices)} {platform} device(s), "
        f"{os.cpu_count()} host CPUs")
    if platform != "tpu":
        print(f"chip_smoke: JAX's default platform is {platform!r}, not "
              "'tpu'; this script only runs on a TPU", file=sys.stderr)
        return 1
    try:
        if args.chips == 4:
            four_chips(args.seed)
        else:
            one_chip(args.seed, devices[0])
    except Exception:
        traceback.print_exc()
        return 1
    emit(ok=True, device={"platform": platform,
                          "kind": devices[0].device_kind,
                          "count": len(devices)})
    return 0


if __name__ == "__main__":
    sys.exit(main())
