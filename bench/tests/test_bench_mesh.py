"""A cell of four chips joins the benchmark with new files alone.

In a copy of the benchmark, ``kron-s9.solve-mesh4`` is added as new
files and list entries only: a configuration, a traffic mix naming a
loop of its own, that loop, and a per-layer metric.  The loop solves
over a ``("data",)`` mesh of the cell's chips (``solve(graph,
mesh=...)``) on edges placed on that mesh at set-up, so every round of
the solve exchanges labels between chips.  Its runs go to a child
process that sees four CPU devices.  A sound run is correct; the control
(half the sweeps) and a run whose chips leave out that exchange are not.
"""
import json
import os

import pytest

from bench import harness
from bench.tests.conftest import copy_benchmark, run_tiny_on_devices, write_new
from bench.tests.test_bench_registry import check_cells

CELL = "kron-s9.solve-mesh4"
CHIPS = 4

# whole solve(graph, mesh=...) calls over the cell's chips, labels to host
MESH_LOOP = '''
import contextlib
import time

import numpy as np

import repro
from bench import drive, reference


class Loop:
    def __init__(self, cell, seed, src, dst, n):
        self.mesh = drive.mesh_of(cell)
        self.src, self.dst = drive.place_edges(self.mesh, src, dst)
        self.n = n
        self.graph = repro.Graph(src=self.src, dst=self.dst, n_vertices=n)
        self.labels = []
        with drive.span("warmup"):
            res = repro.solve(self.graph, mesh=self.mesh)
            np.asarray(res.labels)
        for p in drive.plans(res.provenance):
            print(p, flush=True)
        drive.log(f"warm-up solve: {int(res.iterations)} rounds on "
                  f"{cell.chips} chips, m = {int(src.shape[0])}")

    def window(self, seconds):
        iterations, failed = [], 0
        t0 = time.perf_counter()
        while True:
            with drive.span("solve"):
                res = repro.solve(self.graph, mesh=self.mesh)
            with drive.span("labels_to_host"):
                labels = np.asarray(res.labels)
            t = time.perf_counter() - t0
            self.labels.append(labels)
            iterations.append(int(res.iterations))
            failed += bool(drive.fallbacks(res.provenance))
            if t >= seconds:
                break
        return {"ops": len(iterations), "failed": failed, "window_s": t,
                "solve_s": t / len(iterations), "iterations": iterations,
                "sweeps": sum(iterations), "n": self.n,
                "m": int(self.src.shape[0])}

    def check(self):
        src, dst = np.asarray(self.src), np.asarray(self.dst)
        del self.graph, self.src, self.dst
        with drive.span("reference"):
            want = reference.component_labels(src, dst, self.n)
        worst = max(reference.mismatches(got, want) for got in self.labels)
        return {"mismatched_vertices": (worst, 0)}


@contextlib.contextmanager
def control():
    real = repro.solve
    repro.solve = drive.half_the_sweeps(real)
    try:
        yield
    finally:
        repro.solve = real
'''

# the cell's own per-layer metric: global rounds per solve
ROUNDS_READER = '''
def read(run):
    its = run.counters.get("iterations")
    return sum(its) / len(its) if its else None
'''

# Faults planted in the child (conftest._CHILD): the control, and the
# exchange between chips left out, so that the labels are those of chip
# 0's quarter of the edges alone.
CONTROL = "context = cell.loop.control()"
EXCHANGE_LEFT_OUT = '''
real = repro.solve

def solve(graph, *args, mesh, **kwargs):
    quarter = graph.n_edges // mesh.devices.size
    return real(repro.Graph(src=graph.src[:quarter], dst=graph.dst[:quarter],
                            n_vertices=graph.n_vertices), *args, **kwargs)

repro.solve = solve
'''


def _add_mesh_cell(root):
    bench = os.path.join(root, "bench")
    write_new(os.path.join(bench, "configs", "kron-s9.json"), json.dumps(
        {"name": "kron-s9", "generator": "kronecker", "scale": 9,
         "edge_factor": 8, "initiator": [0.45, 0.15, 0.15]}))
    write_new(os.path.join(bench, "traffic", "solve-mesh4.json"),
              json.dumps({"loop": "solve-mesh", "about": "test"}))
    write_new(os.path.join(bench, "loops", "solve-mesh.py"), MESH_LOOP)
    write_new(os.path.join(bench, "metrics", "rounds.mesh.py"),
              ROUNDS_READER)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        bm = json.load(f)
    bm["configs"].append({"name": "kron-s9", "source": "test",
                          "file": "bench/configs/kron-s9.json",
                          "reduced": [], "why": "test"})
    bm["workloads"].append({"name": CELL, "config": "kron-s9",
                            "traffic": "solve-mesh4", "chips": CHIPS,
                            "why": "test"})
    for m in bm["end_to_end"]:
        if m["name"] == "solve_s":
            m["workloads"].append(CELL)
    bm["per_layer"].append(
        {"name": "rounds.mesh", "unit": "count", "better": "lower",
         "source": "program_counter", "layer": "Contour fixpoint",
         "moves": "solve_s", "workloads": [CELL]})
    with open(path, "w") as f:
        json.dump(bm, f)


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    root = tmp_path_factory.mktemp("checkout")
    before = copy_benchmark(root)
    _add_mesh_cell(str(root))
    for p, data in before.items():
        assert open(p, "rb").read() == data
    return root


def _run(root, fault=None, trace=False):
    return run_tiny_on_devices(root, CELL, CHIPS, {}, {}, fault=fault,
                               trace=trace)


def test_the_mesh_cell_resolves_on_four_chips(root):
    check_cells(root)
    cell = harness.resolve(str(root), CELL)
    assert cell.chips == CHIPS
    for module in (cell.generator, cell.loop):
        assert module.__file__.startswith(str(root))
    assert [m["name"] for m, _ in cell.per_layer] == ["rounds.mesh"]
    assert {m["name"] for m in cell.end_to_end} == {"solve_s", "setup_s"}


def test_a_sound_run_on_four_devices_is_correct(root):
    line, checks = _run(root)
    assert line["correct"] and line["failed"] == 0
    assert checks == {"mismatched_vertices": (0, 0)}
    assert set(line["metrics"]) == {"solve_s", "setup_s"}
    dev = line["device"]
    assert dev["platform"] == "cpu" and dev["count"] == dev["chips"] == CHIPS
    # one reading per chip; the CPU may keep none
    assert len(dev["memory_peak_bytes_per_chip"]) == CHIPS
    assert list(line)[-1] == "checks"


def test_a_traced_run_reads_the_cells_own_metric(root):
    line, _ = _run(root, trace=True)
    assert line["correct"]
    assert line["metrics"]["rounds.mesh"]["value"] >= 1


@pytest.mark.parametrize("fault", [CONTROL, EXCHANGE_LEFT_OUT],
                         ids=["control", "exchange_left_out"])
def test_a_broken_mesh_run_is_not_correct(root, fault):
    line, checks = _run(root, fault=fault)
    assert not line["correct"]
    assert checks["mismatched_vertices"][0] > 0
