"""Cells of ``BENCHMARK.json`` shrunk to sizes a CPU test run holds.

The harness is driven below its look for a chip (``harness.run_cell``
rather than ``bench/run.py``), on the CPU, where the planner picks the
XLA sweep.  A cell of several chips runs in a child process that sees
as many CPU devices (``run_tiny_on_devices``); this process sees one.
"""
import json
import os
import shutil
import subprocess
import sys
import textwrap
import time

import pytest

from bench import harness

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
SRC = os.path.join(ROOT, "src")
# the most a child run of a multi-device cell may take
CHILD_TIMEOUT_S = 300
# a test run's seed, wider than 31 bits as a run's may be, and window
SEED = 2**31 + 11
SECONDS = 0.2

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


def run_tiny(cell: harness.Cell, seed: int = SEED,
             seconds: float = SECONDS, trace_dir=None):
    """``(result line, checks)`` of one run of ``cell``."""
    return harness.run_cell(cell, seed, seconds, trace_dir is not None,
                            time.perf_counter(), trace_dir)


# One run of a cell in a child process; its argument is a JSON object of
# run_tiny_on_devices's arguments.  ``fault`` is Python run before the
# run with ``cell`` and ``repro`` in scope; it may bind ``context`` to a
# context manager the run goes under.
_CHILD = textwrap.dedent("""
    import contextlib, json, shutil, sys, tempfile, time
    args = json.loads(sys.argv[1])
    sys.path[:0] = [args["root"], args["src"]]
    import repro
    from bench import harness
    cell = harness.resolve(args["root"], args["name"])
    cell.config.update(args["config"])
    cell.traffic.update(args["traffic"])
    scope = {"cell": cell, "repro": repro,
             "context": contextlib.nullcontext()}
    if args["fault"]:
        exec(args["fault"], scope)
    trace_dir = (tempfile.mkdtemp(prefix="bench-trace-") if args["trace"]
                 else None)
    try:
        with scope["context"]:
            line, checks = harness.run_cell(
                cell, args["seed"], args["seconds"], args["trace"],
                time.perf_counter(), trace_dir)
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
    print(json.dumps({"line": line, "checks": checks}))
""")


def run_tiny_on_devices(root: str, name: str, devices: int,
                        tiny_config: dict, tiny_traffic: dict, fault=None,
                        trace: bool = False):
    """``(result line, checks)`` of one run of the cell ``name`` of
    ``<root>/BENCHMARK.json``, in a child process on ``devices`` CPU
    devices, its configuration and traffic updated by ``tiny_config`` and
    ``tiny_traffic``, with the Python ``fault`` planted (see ``_CHILD``)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=" ".join(filter(None, [
                   os.environ.get("XLA_FLAGS"),
                   f"--xla_force_host_platform_device_count={devices}"])))
    args = {"root": str(root), "src": SRC, "name": name,
            "config": tiny_config, "traffic": tiny_traffic, "fault": fault,
            "seed": SEED, "seconds": SECONDS, "trace": trace}
    try:
        proc = subprocess.run([sys.executable, "-c", _CHILD, json.dumps(args)],
                              cwd=str(root), env=env, capture_output=True,
                              text=True, timeout=CHILD_TIMEOUT_S)
    except subprocess.TimeoutExpired as e:
        err = e.stderr.decode() if isinstance(e.stderr, bytes) else e.stderr
        pytest.fail(f"{name} on {devices} devices ran past "
                    f"{CHILD_TIMEOUT_S} s:\n{(err or '')[-4000:]}")
    if proc.returncode != 0:
        pytest.fail(f"{name} on {devices} devices exited "
                    f"{proc.returncode}:\n{proc.stderr[-4000:]}")
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    return out["line"], {k: tuple(v) for k, v in out["checks"].items()}


def copy_benchmark(root) -> dict:
    """``BENCHMARK.json`` and the benchmark's files copied into ``root``;
    returns each copied file's bytes, by path."""
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    shutil.copytree(os.path.join(ROOT, "bench"), os.path.join(root, "bench"),
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    return {p: open(p, "rb").read()
            for d, _, files in os.walk(os.path.join(root, "bench"))
            for p in (os.path.join(d, f) for f in files)}


def write_new(path, text):
    """Write a file that must not exist yet."""
    assert not os.path.exists(path), path
    with open(path, "w") as f:
        f.write(text)
