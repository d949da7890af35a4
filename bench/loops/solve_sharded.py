"""Whole ``solve(graph, mesh=...)`` calls over the cell's chips, labels
to host.

The ``solve`` loop (``bench/loops/solve.py``: one client, closed loop,
its check and its control) with the configuration's whole edge list
split into one contiguous block per chip of a ``("data",)`` mesh of the
cell's chips (``bench.drive.mesh_of``): each round of a solve sweeps
every chip's block, then exchanges the labels between the chips.  A
generator that draws on that mesh leaves ``place_edges`` nothing to
move.  Traffic keys: none.
"""
from __future__ import annotations

import time

import numpy as np

import repro
from bench.drive import fallbacks, log, mesh_of, place_edges, plans, span
from bench.loops import solve as whole

control = whole.control


class Loop(whole.Loop):

    def __init__(self, cell, seed: int, src, dst, n: int):
        self.mesh = mesh_of(cell)
        self.src, self.dst = place_edges(self.mesh, src, dst)
        self.n = n
        self.graph = repro.Graph(src=self.src, dst=self.dst, n_vertices=n)
        self.labels: list = []
        self.failed = 0
        with span("warmup"):
            res = repro.solve(self.graph, mesh=self.mesh)
            np.asarray(res.labels)
        for p in plans(res.provenance):
            print(p, flush=True)
        log(f"warm-up solve: {int(res.iterations)} rounds on {cell.chips} "
            f"chips, m = {int(src.shape[0])}")

    def window(self, seconds: float) -> dict:
        iterations = []
        t0 = time.perf_counter()
        while True:
            with span("solve"):
                res = repro.solve(self.graph, mesh=self.mesh)
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
