#!/usr/bin/env python3
"""Run one benchmark cell once on a TPU and print its result line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

From the root of a checkout.  The cell is an entry of ``workloads`` in
``BENCHMARK.json``.  In order, the run:

1. refuses any platform but ``tpu``, and fewer chips than the cell asks;
2. draws the cell's graph from ``--seed`` (``bench/generators/``), on the
   device where the generator can;
3. warms up the cell's own shapes (set-up ends here: ``setup_s``), with
   JAX's compile cache in ``.jax_cache/`` at the root of the checkout;
4. drives the program for ``--seconds`` through its public entry points
   with default options, by the traffic's loop (``bench/loops/``); with
   ``--trace 1`` the window is recorded by ``jax.profiler`` and the
   per-layer metrics are read from that trace (``bench/trace.py``,
   ``bench/metrics/``);
5. frees the program's state, compares what the window produced with the
   plain reference (``bench/reference.py``), and prints one JSON line,
   last on stdout, with the numbers compared, each beside its limit,
   under ``checks`` and again as the last lines on stderr.

The plan the planner picked is printed on an earlier line.  The run
exits non-zero, printing no result, off a TPU, with too few chips, or
where the program (``src/repro``) is missing.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SRC = os.path.join(ROOT, "src")
CACHE_DIR = os.path.join(ROOT, ".jax_cache")


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def configure_jax():
    """Import JAX with the compile cache at its fixed path, caching every
    program, however quick to compile, so a second run compiles none."""
    import jax

    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return jax


def main(argv=None) -> int:
    args = parse(argv)
    if args.seed < 0 or args.seconds <= 0:
        print("bench: --seed must be >= 0 and --seconds > 0",
              file=sys.stderr)
        return 2
    if not os.path.isdir(os.path.join(SRC, "repro")):
        print(f"bench: no program under {SRC}; run from a checkout of the "
              "repository", file=sys.stderr)
        return 2
    sys.path[:0] = [ROOT, SRC]
    from bench import harness

    try:
        cell = harness.resolve(ROOT, args.workload)
    except (KeyError, FileNotFoundError) as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    t_imports = time.perf_counter()
    jax = configure_jax()
    devices = jax.devices()
    print(f"bench: imports {t_imports - T_START:.3f} s, JAX and TPU start "
          f"{time.perf_counter() - t_imports:.3f} s", file=sys.stderr)
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"bench: {args.workload} needs {cell.chips} TPU chip(s); JAX "
              f"found {len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return 1
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if args.trace else None
    try:
        line, checks = harness.run_cell(cell, args.seed, args.seconds,
                                        bool(args.trace), T_START, trace_dir)
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
    harness.print_result(line, checks)
    return 0


if __name__ == "__main__":
    sys.exit(main())
