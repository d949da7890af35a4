"""Cells, configurations, traffic mixes and metrics are found by name."""
import json
import os
import re

import pytest

from bench import harness
from bench.tests.conftest import (ROOT, copy_benchmark, run_tiny,
                                  write_new)

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def check_cells(root):
    """Every cell of ``<root>/BENCHMARK.json`` resolves to its files, on 1
    or 4 chips, with at most half the cells (rounded down, or one where
    there are fewer) on 4."""
    bm = harness.load_benchmark(root)
    e2e = {m["name"] for m in bm["end_to_end"]}
    four = 0
    for w in bm["workloads"]:
        cell = harness.resolve(root, w["name"])
        assert cell.chips in (1, 4)
        four += cell.chips == 4
        names = {m["name"] for m in cell.end_to_end}
        # setup_s and one more end-to-end metric, and one per-layer one
        # that moves a metric this cell reports
        assert "setup_s" in names and len(names) >= 2
        assert cell.per_layer
        assert all(m["moves"] in names for m, _ in cell.per_layer)
    for entry in bm["end_to_end"] + bm["per_layer"] + bm["workloads"] \
            + bm["configs"]:
        assert NAME.match(entry["name"]), entry["name"]
    for m in bm["per_layer"]:
        assert m["moves"] in e2e
    assert four <= max(1, len(bm["workloads"]) // 2)


def test_every_cell_of_the_benchmark_resolves():
    check_cells(ROOT)


def test_more_than_half_the_cells_on_four_chips_is_refused(tmp_path):
    copy_benchmark(tmp_path)
    path = tmp_path / "BENCHMARK.json"
    bm = json.loads(path.read_text())
    cells = bm["workloads"]
    allowed = max(1, len(cells) // 2)
    for four in (allowed, allowed + 1):
        for i, w in enumerate(cells):
            w["chips"] = 4 if i < four else 1
        path.write_text(json.dumps(bm))
        if four == allowed:
            check_cells(tmp_path)
        else:
            with pytest.raises(AssertionError):
                check_cells(tmp_path)


# a loop of its own: a stream from no edges at all, fed batches from a
# batch generator of its own, for a configuration of its own
COLD_LOOP = """
import time
import numpy as np
import repro
from bench import harness, reference


class Loop:
    def __init__(self, cell, seed, src, dst, n):
        gen = harness.load_module(harness.find(
            cell.root, "bench", "batches", cell.config["generator"] + ".py"))
        self.n = n
        self.batches = gen.Batches(cell.config, seed,
                                   cell.traffic["batch_edges"], 1)
        self.eng = repro.StreamingConnectivity(n)
        self.fed = []
        self._ingest()

    def _ingest(self):
        s, d = self.batches.chunk(len(self.fed))[0]
        self.eng.ingest(s, d)
        self.eng.labels.block_until_ready()
        self.fed.append((np.asarray(s), np.asarray(d)))

    def window(self, seconds):
        b, t0 = len(self.fed), time.perf_counter()
        while time.perf_counter() - t0 < seconds:
            self._ingest()
        t = time.perf_counter() - t0
        b = len(self.fed) - b
        return {"ops": b, "failed": 0, "window_s": t, "batches": b,
                "ingest_edges_per_s": b * self.batches.k / t}

    def check(self):
        got = np.asarray(self.eng.labels)
        src, dst = (np.concatenate(x) for x in zip(*self.fed))
        want = reference.component_labels(src, dst, self.n)
        return {"mismatched_vertices": (reference.mismatches(got, want), 0)}


def control():
    raise NotImplementedError
"""

RING_GENERATOR = """
import jax.numpy as jnp


def draw(cfg, seed):
    n = cfg["n"]
    return jnp.arange(n - 1), jnp.arange(1, n), n
"""

RING_BATCHES = """
import jax
import jax.numpy as jnp


class Batches:
    def __init__(self, cfg, seed, k, count):
        self.n, self.k, self.count, self.seed = cfg["n"], k, count, seed

    def chunk(self, index):
        key = jax.random.fold_in(jax.random.PRNGKey(self.seed % 2**31),
                                 index)
        e = jax.random.randint(key, (self.count, 2, self.k), 0, self.n)
        return [(b[0], b[1]) for b in e]
"""


def _add_cells(root):
    """Two cells added as files, with one entry each in BENCHMARK.json;
    no file that was there is touched.  ``kron-s9.solve-w2``: a
    configuration, a traffic mix and a per-layer metric for the solve
    loop.  ``ring-9.cold``: a traffic loop, a graph generator and a
    batch generator of its own besides."""
    bench = os.path.join(root, "bench")
    write_new(os.path.join(bench, "configs", "kron-s9.json"), json.dumps(
        {"name": "kron-s9", "generator": "kronecker", "scale": 9,
         "edge_factor": 8, "initiator": [0.45, 0.15, 0.15]}))
    write_new(os.path.join(bench, "traffic", "solve-w2.json"),
              json.dumps({"loop": "solve", "about": "test"}))
    write_new(os.path.join(bench, "metrics", "solves.window.py"),
              "def read(run):\n"
              "    return float(run.counters['ops'])\n")
    write_new(os.path.join(bench, "configs", "ring-9.json"),
              json.dumps({"name": "ring-9", "generator": "ring", "n": 512}))
    write_new(os.path.join(bench, "generators", "ring.py"), RING_GENERATOR)
    write_new(os.path.join(bench, "batches", "ring.py"), RING_BATCHES)
    write_new(os.path.join(bench, "loops", "cold-stream.py"), COLD_LOOP)
    write_new(os.path.join(bench, "traffic", "cold.json"),
              json.dumps({"loop": "cold-stream", "batch_edges": 64}))
    write_new(os.path.join(bench, "metrics", "batches.cold.py"),
              "def read(run):\n"
              "    return run.counters.get('batches')\n")
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        bm = json.load(f)
    for name in ("kron-s9", "ring-9"):
        bm["configs"].append({"name": name, "source": "test",
                              "file": f"bench/configs/{name}.json",
                              "reduced": [], "why": "test"})
    bm["workloads"] += [
        {"name": "kron-s9.solve-w2", "config": "kron-s9",
         "traffic": "solve-w2", "chips": 1, "why": "test"},
        {"name": "ring-9.cold", "config": "ring-9", "traffic": "cold",
         "chips": 1, "why": "test"}]
    for m in bm["end_to_end"]:
        if m["name"] == "solve_s":
            m["workloads"].append("kron-s9.solve-w2")
        if m["name"] == "ingest_edges_per_s":
            m["workloads"].append("ring-9.cold")
    bm["per_layer"] += [
        {"name": "solves.window", "unit": "count", "better": "higher",
         "source": "program_counter", "layer": "test", "moves": "solve_s",
         "workloads": ["kron-s9.solve-w2"]},
        {"name": "batches.cold", "unit": "count", "better": "higher",
         "source": "program_counter", "layer": "test",
         "moves": "ingest_edges_per_s", "workloads": ["ring-9.cold"]}]
    with open(path, "w") as f:
        json.dump(bm, f)


@pytest.fixture(scope="module")
def added(tmp_path_factory):
    root = tmp_path_factory.mktemp("checkout")
    before = copy_benchmark(root)
    _add_cells(str(root))
    for p, data in before.items():
        assert open(p, "rb").read() == data
    return root


@pytest.mark.parametrize("name,e2e,metric", [
    ("kron-s9.solve-w2", "solve_s", "solves.window"),
    ("ring-9.cold", "ingest_edges_per_s", "batches.cold")])
def test_an_added_cell_is_found_by_name_and_runs(added, name, e2e, metric):
    cell = harness.resolve(str(added), name)
    for module in (cell.generator, cell.loop):
        assert module.__file__.startswith(str(added))
    assert [m["name"] for m, _ in cell.per_layer] == [metric]
    assert {m["name"] for m in cell.end_to_end} == {e2e, "setup_s"}

    line, _ = run_tiny(cell)
    assert line["correct"] and set(line["metrics"]) == {e2e, "setup_s"}
    line, _ = run_tiny(cell, trace_dir=str(added / ("trace-" + name)))
    assert line["metrics"][metric]["value"] == line["attempted"]
