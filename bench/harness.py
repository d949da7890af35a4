"""Find a cell's files by name, run the cell once, and build its result.

Everything that belongs to one configuration, traffic mix or per-layer
metric sits in a file of its own, found by the name ``BENCHMARK.json``
gives it:

* configuration: the ``file`` of its ``configs`` entry, which names its
  ``generator``, ``bench/generators/<generator>.py`` (``draw()``);
* traffic mix: ``bench/traffic/<traffic>.json``, whose parameters name
  their ``loop``, ``bench/loops/<loop>.py`` (see ``bench/drive.py``); a
  loop may find more files by name, as the stream loop finds its batches
  in ``bench/batches/<generator>.py``;
* per-layer metric: ``bench/metrics/<name>.py``, whose ``read(run)``
  returns the number, or None where it finds nothing to read.

A later cell is added with files, one ``workloads`` entry, and its name
appended to the ``workloads`` lists of the metrics it reports; no file
here changes.  That holds for a cell of several chips too: its loop
builds its mesh and places its edges with ``bench/drive.py``, and the
result's ``device`` reads the memory of every chip the cell uses.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import sys
import time
from typing import Optional

BENCH_DIR = "bench"
# the runtime's event for each executable it builds (or loads from the
# persistent cache) for a new shape
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


@dataclasses.dataclass
class Cell:
    """One workload of ``BENCHMARK.json`` with everything it names."""

    root: str                   # the checkout the files were found in
    name: str
    chips: int
    config: dict
    traffic: dict
    generator: object           # module with draw()
    loop: object                # module with Loop and control()
    end_to_end: list            # metric entries this cell reports
    per_layer: list             # (metric entry, reader) pairs


@dataclasses.dataclass
class Run:
    """What a per-layer metric reader may read."""

    cell: Cell
    counters: dict              # from the traffic loop's window()
    trace: Optional[object]     # bench.trace.Trace of a traced run
    device_kind: str


def load_module(path: str):
    """Import a Python file by path (generator, loop and metric files are
    found by name, not installed as modules)."""
    name = "bench_file_" + os.path.relpath(path).replace(os.sep, "_") \
        .replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def find(root: str, *parts: str) -> str:
    """The file ``<root>/<parts...>``, or FileNotFoundError naming it."""
    path = os.path.join(root, *parts)
    if not os.path.isfile(path):
        raise FileNotFoundError(f"no file {os.path.relpath(path, root)}")
    return path


def load_benchmark(root: str) -> dict:
    with open(find(root, "BENCHMARK.json")) as f:
        return json.load(f)


def resolve(root: str, name: str) -> Cell:
    """The cell ``name`` of ``<root>/BENCHMARK.json``, with its files."""
    bm = load_benchmark(root)
    cells = {w["name"]: w for w in bm["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; have {sorted(cells)}")
    w = cells[name]
    entry = {c["name"]: c for c in bm["configs"]}[w["config"]]
    with open(find(root, entry["file"])) as f:
        config = json.load(f)
    with open(find(root, BENCH_DIR, "traffic", w["traffic"] + ".json")) as f:
        traffic = json.load(f)
    generator = load_module(
        find(root, BENCH_DIR, "generators", config["generator"] + ".py"))
    loop = load_module(find(root, BENCH_DIR, "loops", traffic["loop"] + ".py"))
    e2e = [m for m in bm["end_to_end"]
           if name in m.get("workloads", [name])]
    reported = {m["name"] for m in e2e}
    per_layer = []
    for m in bm["per_layer"]:
        if ("workloads" in m and name in m["workloads"]) or \
                ("workloads" not in m and m["moves"] in reported):
            reader = load_module(
                find(root, BENCH_DIR, "metrics", m["name"] + ".py")).read
            per_layer.append((m, reader))
    return Cell(root=root, name=name, chips=int(w["chips"]), config=config,
                traffic=traffic, generator=generator, loop=loop,
                end_to_end=e2e, per_layer=per_layer)


class CompileCounter:
    """Counts the executables the runtime builds while ``counting``."""

    def __init__(self):
        self.count = 0
        self.counting = False

    def __call__(self, event: str, duration_secs: float, **kwargs) -> None:
        if self.counting and event == COMPILE_EVENT:
            self.count += 1


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             t_start: float, trace_dir: Optional[str] = None):
    """Run ``cell`` once; returns ``(result line dict, checks)``.

    ``t_start`` is the process's start on ``time.perf_counter()``; set-up
    runs from it to the first measured operation.  With ``trace`` the
    window is recorded by ``jax.profiler`` into ``trace_dir`` and the
    result carries the per-layer metrics instead of the end-to-end ones.
    """
    import jax

    from bench import drive
    from bench import trace as tr

    device = jax.devices()[0]
    t_draw = time.perf_counter()
    with jax.profiler.TraceAnnotation("draw"):
        src, dst, n = cell.generator.draw(cell.config, seed)
        jax.block_until_ready((src, dst))
    t_warm = time.perf_counter()
    loop = cell.loop.Loop(cell, seed, src, dst, n)
    del src, dst
    counter = CompileCounter()
    jax.monitoring.register_event_duration_secs_listener(counter)
    setup_s = time.perf_counter() - t_start
    if trace:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1      # the benchmark's own spans
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    counter.counting = True
    try:
        with jax.profiler.TraceAnnotation("window"):
            counters = loop.window(seconds)
    finally:
        counter.counting = False
        if trace:
            jax.profiler.stop_trace()
        jax.monitoring.unregister_event_duration_listener(counter)
    counters["compiles_in_window"] = counter.count
    counters["setup_s"] = setup_s
    # each chip the cell uses; None where the backend keeps no statistic
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.devices()[:cell.chips]]
    t_check = time.perf_counter()
    checks = loop.check()
    del loop
    drive.log(f"start to draw {t_draw - t_start:.3f} s, draw "
              f"{t_warm - t_draw:.3f} s, warm-up "
              f"{setup_s - (t_warm - t_start):.3f} s, window "
              f"{counters['window_s']:.3f} s, check "
              f"{time.perf_counter() - t_check:.3f} s")

    known = [p for p in peaks if p is not None]
    dev = {"platform": device.platform, "kind": device.device_kind,
           "count": len(jax.devices()), "chips": cell.chips,
           "memory_peak_bytes": max(known) if known else None,
           "memory_peak_bytes_per_chip": peaks}
    metrics = {}
    breakdown = None
    if trace:
        t = tr.load(trace_dir)
        lo, hi = t.window()
        dev["busy_s"] = t.busy_ns() / 1e9
        dev["window_s"] = (hi - lo) / 1e9
        run = Run(cell=cell, counters=counters, trace=t,
                  device_kind=device.device_kind)
        for m, reader in cell.per_layer:
            value = reader(run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        breakdown = {"device_ops": t.ops_by_name(), "idle_gaps": t.idle_gaps()}
    else:
        for m in cell.end_to_end:
            if m["name"] not in counters:
                raise KeyError(f"the {cell.traffic['loop']!r} loop gives no "
                               f"{m['name']!r}")
            metrics[m["name"]] = {"value": counters[m["name"]],
                                  "unit": m["unit"]}
    line = {"correct": all(v <= lim for v, lim in checks.values()),
            "attempted": counters["ops"], "failed": counters["failed"],
            "metrics": metrics, "device": dev}
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["checks"] = {k: {"value": v, "limit": lim}
                      for k, (v, lim) in checks.items()}
    return line, checks


def print_result(line: dict, checks: dict) -> None:
    """The result line last on stdout, each check last on stderr."""
    print(json.dumps(line), flush=True)
    for k, (v, lim) in checks.items():
        print(f"check {k}: {v} (limit {lim})", file=sys.stderr, flush=True)
