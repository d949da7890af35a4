"""A closed-loop producer of edge batches into a warm-started stream.

The first ``warm_start_fraction`` of the configuration's edges is solved
at set-up and handed to ``StreamingConnectivity(n, warm_start=...)`` with
default options.  The window then ingests batches of ``batch_edges``
fresh edges, one after another, each until its labels are ready on the
device.  The batches come from ``bench/batches/<generator>.py``, found by
the configuration's ``generator``.

Traffic keys: ``warm_start_fraction``, ``batch_edges``,
``warmup_batches`` (ingested at set-up), ``max_edges_ingested`` and
``chunk_batches``.  Set-up draws every batch that warm-up and window may
ingest, ``max_edges_ingested`` edges in device calls of ``chunk_batches``
batches each, so that the window holds nothing but ingests; it also
compiles the edge store's growth up to that size.  The window ends
early, saying so, once it has ingested them all.  Size it to what a
window can reach, with some room: the batches and the warm-up of the
store's growth take device memory in proportion.
"""
from __future__ import annotations

import contextlib
import time

import numpy as np

import repro
from bench import harness, reference
from bench.drive import fallbacks, half_the_sweeps, log, next_pow2, plans, \
    span


class Loop:

    def __init__(self, cell, seed: int, src, dst, n: int):
        t = cell.traffic
        self.n = n
        self.k = int(t["batch_edges"])
        self.max_batches = int(t["max_edges_ingested"]) // self.k
        k_half = int(int(src.shape[0]) * float(t["warm_start_fraction"]))
        self.half = (src[:k_half], dst[:k_half])
        del src, dst
        gen = harness.load_module(harness.find(
            cell.root, harness.BENCH_DIR, "batches",
            cell.config["generator"] + ".py"))
        batches = gen.Batches(cell.config, seed, self.k,
                              int(t["chunk_batches"]))
        with span("draw"):
            self.pool = [b for j in range(-(-self.max_batches
                                              // batches.count))
                         for b in batches.chunk(j)][:self.max_batches]
        self.n_batches = 0
        self._warm_edge_store()
        with span("warmup"):
            res = repro.solve(repro.Graph(src=self.half[0], dst=self.half[1],
                                          n_vertices=n))
            res.labels.block_until_ready()
        self.failed = len(fallbacks(res.provenance))
        self.eng = repro.StreamingConnectivity(n, warm_start=res)
        del res
        with span("warmup"):
            for _ in range(int(t["warmup_batches"])):
                self._ingest_next()
        for p in plans(self.eng.snapshot().provenance):
            print(p, flush=True)

    def _ingest_next(self) -> float:
        """Ingest the next batch until its labels are ready on the device;
        returns the seconds from ingest to visible."""
        s, d = self.pool[self.n_batches]
        t0 = time.perf_counter()
        with span("ingest"):
            self.eng.ingest(s, d)
            self.eng.labels.block_until_ready()
        self.n_batches += 1
        return time.perf_counter() - t0

    def _warm_edge_store(self) -> None:
        """Compile the edge store's growth at every capacity up to
        ``max_edges_ingested``, on a throwaway stream, before the real
        one holds anything, so that nothing compiles inside the window."""
        s, d = self.pool[0]
        tmp = repro.StreamingConnectivity(self.n)
        cap = next_pow2(self.k)
        with span("warmup"):
            while cap <= next_pow2(self.max_batches * self.k):
                tmp._ensure_capacity(cap)
                tmp.ingest(s, d)
                tmp.labels.block_until_ready()
                cap *= 2
        del tmp

    def window(self, seconds: float) -> dict:
        it0 = int(self.eng.snapshot().iterations)
        first = self.n_batches
        latencies = []
        t0 = time.perf_counter()
        while True:
            latencies.append(self._ingest_next())
            t = time.perf_counter() - t0
            if t >= seconds or self.n_batches == self.max_batches:
                break
        if t < seconds:
            log(f"window ended at {len(latencies)} batches, {t:.3f} s: the "
                "traffic's max_edges_ingested was reached")
        snap = self.eng.snapshot()
        self.failed += len(fallbacks(snap.provenance))
        b = self.n_batches - first
        slow = sorted(range(b), key=lambda i: -latencies[i])[:5]
        q = np.percentile(latencies, [5, 25, 50, 75, 95]) * 1e3
        log("ingest ms p5/p25/p50/p75/p95 "
            + "/".join(f"{x:.3f}" for x in q)
            + f", mean {t / b * 1e3:.3f}; slowest "
            + ", ".join(f"#{i} {latencies[i] * 1e3:.1f}" for i in slow)
            + f"; {(int(snap.iterations) - it0) / b:.4f} sweeps a batch; "
            f"edge store {self.eng.capacity} slots")
        return {"ops": b, "failed": self.failed, "window_s": t,
                "ingest_p95_ms": float(q[4]),
                "ingest_edges_per_s": b * self.k / t,
                "iterations_per_batch": (int(snap.iterations) - it0) / b,
                "batches": b, "n": self.n}

    def check(self) -> dict:
        got = np.asarray(self.eng.labels)
        del self.eng
        src = [np.asarray(self.half[0])]
        dst = [np.asarray(self.half[1])]
        for s, d in self.pool[:self.n_batches]:
            src.append(np.asarray(s))
            dst.append(np.asarray(d))
        del self.half, self.pool
        with span("reference"):
            want = reference.component_labels(np.concatenate(src),
                                              np.concatenate(dst), self.n)
        return {"mismatched_vertices": (reference.mismatches(got, want), 0)}


@contextlib.contextmanager
def control():
    """The warm start's solve cut to half its sweeps, and each delta solve
    of the stream to one sweep."""
    real_solve, real_stream = repro.solve, repro.StreamingConnectivity
    repro.solve = half_the_sweeps(real_solve)
    repro.StreamingConnectivity = lambda n, *a, **kw: real_stream(
        n, *a, max_iters=1, **kw)
    try:
        yield
    finally:
        repro.solve, repro.StreamingConnectivity = real_solve, real_stream
