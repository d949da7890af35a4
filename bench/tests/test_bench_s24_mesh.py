"""``graph500-s24.solve-4chip``: the sharded solve at LDBC's graph500-24,
run from the committed files at scale 9 on four CPU devices.

Its generator draws each chip's block of the edges on that chip, the
same edges as ``bench/generators/kronecker.py``; its loop solves over the
mesh of the cell's chips.  Runs go to a child process that sees four CPU
devices (``run_tiny_on_devices``).  A sound run is correct; the control
and a run whose chips leave out the exchange are not.  A CPU trace has
no device plane, so the readers of device time read null there; they
are checked on traces of four chips built by hand.
"""
import json
import os
import subprocess
import sys
import textwrap

import pytest

from bench import harness, roofline
from bench import program_trace as pt
from bench import trace as tr
from bench.tests.conftest import ROOT, SEED, SRC, run_tiny_on_devices
from bench.tests.test_bench_mesh import CONTROL, EXCHANGE_LEFT_OUT

CELL = "graph500-s24.solve-4chip"
CHIPS = 4
TINY = {"scale": 9}
METRICS = ["iterations.solve", "sweep_ms.solve", "idle_pct.solve",
           "scatter_min_ms.solve", "fixpoint_ms.solve",
           "label_allreduce_ms.solve", "sweep_roofline.shard"]


def _read(name):
    return harness.load_module(harness.find(ROOT, "bench", "metrics",
                                            name + ".py")).read


def _run(fault=None, trace=False):
    return run_tiny_on_devices(ROOT, CELL, CHIPS, TINY, {}, fault=fault,
                               trace=trace)


def test_the_cell_resolves_with_its_configuration():
    cell = harness.resolve(ROOT, CELL)
    assert cell.chips == CHIPS
    assert [m["name"] for m, _ in cell.per_layer] == METRICS
    assert {m["name"] for m in cell.end_to_end} == {"solve_s", "setup_s"}
    cfg = cell.config
    assert cfg["scale"] == 24 and cfg["edge_factor"] == 16
    assert cfg["vertices"] == 1 << cfg["scale"]
    assert cfg["edges"] == cfg["edge_factor"] * cfg["vertices"]
    assert cfg["shards"] == CHIPS and cfg["reduced"] == {}


# Draws the cell's configuration at scale 9 with the sharded generator
# and with kronecker.draw, and prints, for each device in the mesh's
# order, the block of the edge list it holds and whether that block is
# kronecker.draw's.
_DRAW = textwrap.dedent("""
    import json, sys
    import numpy as np
    args = json.loads(sys.argv[1])
    sys.path[:0] = [args["root"], args["src"]]
    from bench import harness
    from bench.generators import kronecker

    cell = harness.resolve(args["root"], args["name"])
    cfg = dict(cell.config, scale=9)
    src, dst, n = cell.generator.draw(cfg, args["seed"])
    want = [np.asarray(a) for a in kronecker.draw(cfg, args["seed"])[:2]]
    order = [d.id for d in src.sharding.mesh.devices.flat]
    blocks = []
    for got, full in ((src, want[0]), (dst, want[1])):
        for sh in sorted(got.addressable_shards,
                         key=lambda sh: order.index(sh.device.id)):
            lo, hi = sh.index[0].start or 0, sh.index[0].stop or len(full)
            blocks.append([lo, hi, bool(np.array_equal(np.asarray(sh.data),
                                                       full[lo:hi]))])
    print(json.dumps({"n": n, "m": len(want[0]), "blocks": blocks}))
""")


def test_the_mesh_draw_is_kroneckers_split_into_quarters():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=" ".join(filter(None, [
                   os.environ.get("XLA_FLAGS"),
                   f"--xla_force_host_platform_device_count={CHIPS}"])))
    args = {"root": ROOT, "src": SRC, "name": CELL, "seed": SEED}
    proc = subprocess.run([sys.executable, "-c", _DRAW, json.dumps(args)],
                          cwd=ROOT, env=env, capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode == 0, proc.stderr[-4000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    m = out["m"]
    assert out["n"] == 512 and m == 16 * 512
    quarter = m // CHIPS
    want = [[i * quarter, (i + 1) * quarter, True] for i in range(CHIPS)]
    assert out["blocks"] == want + want      # src, then dst


def test_a_sound_run_on_four_devices_is_correct():
    line, checks = _run()
    assert line["correct"] and line["failed"] == 0
    assert checks == {"mismatched_vertices": (0, 0)}
    assert set(line["metrics"]) == {"solve_s", "setup_s"}
    dev = line["device"]
    assert dev["platform"] == "cpu" and dev["count"] == dev["chips"] == CHIPS
    assert len(dev["memory_peak_bytes_per_chip"]) == CHIPS


@pytest.mark.parametrize("fault", [CONTROL, EXCHANGE_LEFT_OUT],
                         ids=["control", "exchange_left_out"])
def test_a_broken_run_is_not_correct(fault):
    line, checks = _run(fault=fault)
    assert not line["correct"]
    assert checks["mismatched_vertices"][0] > 0


def test_a_traced_run_is_correct_and_counts_its_rounds():
    line, _ = _run(trace=True)
    assert line["correct"]
    assert line["metrics"]["iterations.solve"]["value"] >= 1


def _four_chips(sweep_ns, exchange_ns, test_ns):
    """A solve window on four chips: per chip a sweep, the label
    exchange and the convergence test's AND-reduce, each of the lengths
    given for that chip, inside one ``solve`` span."""
    body = "jit(_distributed_fixpoint)/shard_map/while/body/"
    ops = []
    for sweep, exchange, test in zip(sweep_ns, exchange_ns, test_ns):
        t1, t2 = sweep, sweep + exchange
        ops.append([(body + "mm_sweep/scatter_min/scatter:", 0, t1),
                    (body + "label_allreduce/pmin:", t1, t2),
                    (body + "converge_check/pmin:", t2, t2 + test)])
    return ops


def test_the_exchange_is_read_per_round_and_averaged_over_chips():
    ops = _four_chips([60, 70, 80, 90], [10, 20, 30, 40], [5, 5, 5, 5])
    spans = [("window", {}, 0, 200), ("solve", {}, 0, 150)]
    window = tr.Trace(device_ops=[], spans=[("window", 0, 200)])
    run = harness.Run(cell=None, counters={"sweeps": 2}, trace=window,
                      device_kind="TPU v5 lite")
    run.program_trace = pt.ProgramTrace(device_ops=ops, spans=spans)
    read = _read("label_allreduce_ms.solve")
    assert read(run) == pytest.approx((10 + 20 + 30 + 40) / 4 / 1e6 / 2)
    # the convergence test's collective is the fixpoint's, not this one
    run.program_trace = pt.ProgramTrace(
        device_ops=[[op for op in chip if "label_allreduce" not in op[0]]
                    for chip in ops], spans=spans)
    assert read(run) is None


def test_the_shard_roofline_counts_one_chips_block_of_the_edges():
    cell = harness.resolve(ROOT, CELL)
    n, m, sweeps = 1 << 24, 1 << 28, 3
    ops = [[("fusion", 0, busy)] for busy in (7e9, 8e9, 9e9, 8e9)]
    t = tr.Trace(device_ops=ops, spans=[("window", 0, 10e9),
                                        ("solve", 0, 10e9)])
    run = harness.Run(cell=cell, counters={"sweeps": sweeps, "n": n, "m": m},
                      trace=t, device_kind="TPU v5 lite")
    read = _read("sweep_roofline.shard")
    want = 100 * sweeps * roofline.sweep_bytes(n, m // CHIPS) / 819e9 / 8.0
    assert read(run) == pytest.approx(want)
    # a whole chip's sweep of all m edges would read about four times that
    whole = _read("sweep_roofline")(run)
    assert whole == pytest.approx(
        want * roofline.sweep_bytes(n, m) / roofline.sweep_bytes(n, m // 4))
    assert read(harness.Run(cell, {"sweeps": 3, "n": n, "m": m}, None,
                            "TPU v5 lite")) is None
