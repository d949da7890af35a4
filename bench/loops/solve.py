"""Whole ``solve(graph)`` calls, one after another, labels to host.

One client, closed loop, on the configuration's whole edge list.  Set-up
runs one solve, which compiles or loads every program the window uses.
The window runs whole solves until ``seconds`` has passed; the last is
counted in full.  Traffic keys: none.
"""
from __future__ import annotations

import contextlib
import time

import numpy as np

import repro
from bench import reference
from bench.drive import fallbacks, half_the_sweeps, log, plans, span


class Loop:

    def __init__(self, cell, seed: int, src, dst, n: int):
        self.src, self.dst, self.n = src, dst, n
        self.graph = repro.Graph(src=src, dst=dst, n_vertices=n)
        self.labels: list = []
        self.failed = 0
        with span("warmup"):
            res = repro.solve(self.graph)
            np.asarray(res.labels)
        for p in plans(res.provenance):
            print(p, flush=True)
        log(f"warm-up solve: {int(res.iterations)} iterations, "
            f"m = {int(src.shape[0])}")

    def window(self, seconds: float) -> dict:
        iterations = []
        t0 = time.perf_counter()
        while True:
            with span("solve"):
                res = repro.solve(self.graph)
            with span("labels_to_host"):
                labels = np.asarray(res.labels)
            t = time.perf_counter() - t0
            self.labels.append(labels)
            iterations.append(int(res.iterations))
            if fallbacks(res.provenance):
                self.failed += 1
            if t >= seconds:
                break
        return {"ops": len(iterations), "failed": self.failed,
                "window_s": t, "solve_s": t / len(iterations),
                "iterations": iterations, "sweeps": sum(iterations),
                "n": self.n, "m": int(self.src.shape[0])}

    def check(self) -> dict:
        src, dst = np.asarray(self.src), np.asarray(self.dst)
        del self.graph, self.src, self.dst
        with span("reference"):
            want = reference.component_labels(src, dst, self.n)
        worst = max(reference.mismatches(got, want) for got in self.labels)
        return {"mismatched_vertices": (worst, 0)}


@contextlib.contextmanager
def control():
    """Each ``solve()`` cut to half the sweeps its sound solve takes."""
    real = repro.solve
    repro.solve = half_the_sweeps(real)
    try:
        yield
    finally:
        repro.solve = real
