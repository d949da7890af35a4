"""Cells of ``BENCHMARK.json`` shrunk to sizes a CPU test run holds.

The harness is driven below its look for a chip (``harness.run_cell``
rather than ``bench/run.py``), on the CPU, where the planner picks the
XLA sweep.
"""
import os
import time

from bench import harness

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

# per configuration: the sizes a test run can hold
TINY_CONFIG = {"graph500-s20": {"scale": 10},
               "delaunay-n20": {"scale": 9}}
TINY_TRAFFIC = {"stream-b16": {"batch_edges": 256, "chunk_batches": 4,
                               "warmup_batches": 2,
                               "max_edges_ingested": 1 << 13}}


def tiny_cell(name: str, root: str = ROOT) -> harness.Cell:
    cell = harness.resolve(root, name)
    w = {w["name"]: w for w in harness.load_benchmark(root)["workloads"]}
    cell.config.update(TINY_CONFIG.get(w[name]["config"], {}))
    cell.traffic.update(TINY_TRAFFIC.get(w[name]["traffic"], {}))
    return cell


def run_tiny(cell: harness.Cell, seed: int = 2**31 + 11,
             seconds: float = 0.2, trace_dir=None):
    """``(result line, checks)`` of one run of ``cell``."""
    return harness.run_cell(cell, seed, seconds, trace_dir is not None,
                            time.perf_counter(), trace_dir)

