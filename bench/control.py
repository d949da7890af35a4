#!/usr/bin/env python3
"""The control of ``correct``, and the readings its limits are set from.

    python bench/control.py --workload <cell> --seeds 1,2,3 \
        --control-seeds 4,5,6 [--seconds 1]

On a TPU, in one process: for each of ``--seeds`` one sound run of the
cell (``bench/run.py``'s path, with a short window), and for each of
``--control-seeds`` one run with the control in the program's place.
Prints one JSON line per run with the numbers compared, then the lower
reading (the largest of the sound runs) and the upper one (the smallest
of the control's).  The benchmark's own runs never run the control.

The system runs no model and states no precision, so the control breaks
the guarantee every configuration states: labels at the solver's
fixpoint.  Each traffic loop names its own control (``control()`` in
``bench/loops/<loop>.py``): the program's own budget path, ``max_iters``,
cut short wherever the loop calls the program, as a later change that
stops early would.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", required=True)
    ap.add_argument("--seconds", type=float, default=1.0)
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from bench import harness
    from bench.run import configure_jax

    jax = configure_jax()
    if jax.devices()[0].platform != "tpu":
        print("bench: the control is read on a TPU", file=sys.stderr)
        return 1
    cell = harness.resolve(ROOT, args.workload)
    readings = {"sound": {}, "control": {}}
    runs = (("sound", args.seeds), ("control", args.control_seeds))
    for mode, seeds in runs:
        for seed in (int(s) for s in seeds.split(",")):
            ctx = (cell.loop.control() if mode == "control"
                   else contextlib.nullcontext())
            with ctx:
                line, checks = harness.run_cell(cell, seed, args.seconds,
                                                False, time.perf_counter())
            for k, (v, _) in checks.items():
                readings[mode].setdefault(k, []).append(v)
            print(json.dumps({"mode": mode, "seed": seed,
                              "correct": line["correct"],
                              "attempted": line["attempted"],
                              "checks": line["checks"]}), flush=True)
    for k in readings["sound"]:
        print(json.dumps({"check": k,
                          "lower": max(readings["sound"][k]),
                          "upper": min(readings["control"].get(k, [None]))}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
