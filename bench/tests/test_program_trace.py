"""The program's scopes and spans read from a trace
(``bench/program_trace.py``), and the per-layer metrics that read them."""
import os
import shutil
import tempfile

import pytest

from bench import harness, program_trace as pt, trace as tr
from bench.tests.conftest import ROOT, run_tiny, tiny_cell

FIXTURES = os.path.join(os.path.dirname(__file__), "fixtures")
# a solve window of the program before it named any scope
UNSCOPED = os.path.join(FIXTURES, "solve_v5e.xplane.pb")
# small scoped windows recorded on a TPU v5e through harness.run_cell:
# graph500 at scale 13 (n = 8192, the blocked kernel), one solve; and a
# warm-started stream of 1024-edge batches on it
SCOPED = {"solve": os.path.join(FIXTURES, "scoped_solve_v5e.trace.json.gz"),
          "stream": os.path.join(FIXTURES,
                                 "scoped_stream_v5e.trace.json.gz")}
SCOPED_XPLANE = os.path.join(FIXTURES, "scoped_solve_v5e.xplane.pb")

NEW_METRICS = ("binning_ms.solve", "kernel_ms.solve", "fixpoint_ms.solve",
               "label_pass_ms.stream", "ingest_host_ms.stream")


def _reader(name):
    return harness.load_module(harness.find(ROOT, "bench", "metrics",
                                            name + ".py")).read


def _run_on(tmp_path, monkeypatch, fixture, counters):
    """A traced run whose trace directory is a ``bench-trace-*`` directory
    of the temp dir holding ``fixture``, as ``bench/run.py`` leaves it."""
    monkeypatch.setattr(tempfile, "tempdir", str(tmp_path))
    prof = tmp_path / "bench-trace-run" / "plugins" / "profile" / "t"
    prof.mkdir(parents=True)
    shutil.copy(fixture, prof / ("host." + os.path.basename(fixture)
                                 .split(".", 1)[1]))
    trace = tr.Trace(device_ops=[], spans=[
        ("window", *pt.load(fixture).window())])
    return harness.Run(cell=None, counters=counters, trace=trace,
                       device_kind="TPU v5 lite")


def test_tf_op_of_the_unscoped_v5e_solve():
    """The recorded solve before any scope: each op's path is flat below
    the while body, and the shares of leaf time are those it had."""
    t = pt.load(UNSCOPED)
    by = t.leaf_ns_by(lambda op: op)
    total = sum(by.values())
    share = {op: ns / total for op, ns in by.items()}
    body = "jit(contour_labels)/while/body/"
    assert share[body + "gather:"] == pytest.approx(0.751, abs=0.002)
    assert share[body + "scatter:"] == pytest.approx(0.107, abs=0.002)
    assert share[body + "scatter-add:"] == pytest.approx(0.101, abs=0.002)
    assert share[body + "pallas_call:"] == pytest.approx(0.028, abs=0.002)
    assert share[body + "jit(argsort)/sort:"] == pytest.approx(0.011,
                                                                abs=0.002)
    assert t.scoped_share() == 0.0
    # the same window and leaf ops as the reduction the benchmark keeps:
    # their summed time exceeds its busy time (their union) by the
    # microsecond or so in which leaves overlap
    base = tr.load(UNSCOPED)
    assert t.window() == base.window()
    assert base.busy_ns() <= sum(by.values()) <= base.busy_ns() + 1e3


def test_every_new_reader_is_null_on_the_unscoped_trace(tmp_path,
                                                        monkeypatch):
    run = _run_on(tmp_path, monkeypatch, UNSCOPED,
                  {"sweeps": 12, "batches": 5})
    assert pt.for_run(run) is not None
    for name in NEW_METRICS:
        assert _reader(name)(run) is None, name


# what the harness read from each window on the chip, with the counters
# of that window (3 sweeps; 11 batches)
ON_CHIP = {"solve": ({"sweeps": 3}, {"binning_ms.solve": 36.36685981799999,
                                     "kernel_ms.solve": 1.2832242186666678,
                                     "fixpoint_ms.solve": 3.8191755726666403}),
           "stream": ({"batches": 11},
                      {"label_pass_ms.stream": 0.1457215341818089,
                       "ingest_host_ms.stream": 4.569568636363636})}


@pytest.mark.parametrize("kind", ["solve", "stream"])
def test_every_reader_reads_its_scoped_trace(tmp_path, monkeypatch, kind):
    counters, read_on_chip = ON_CHIP[kind]
    run = _run_on(tmp_path, monkeypatch, SCOPED[kind], counters)
    for name, value in read_on_chip.items():
        assert _reader(name)(run) == pytest.approx(value, rel=1e-9), name
    t = pt.for_run(run)
    assert t.scoped_share() >= 0.98
    if kind == "solve":
        # the sweep's parts lie inside the sweep
        spans = ["solve", "labels_to_host"]
        sweep = t.scope_ns([pt.MM_SWEEP], spans)
        assert t.scope_ns([pt.BINNING], spans) + t.scope_ns(
            [pt.SCATTER_KERNEL], spans) <= sweep * (1 + 1e-9)
    else:
        ingests = t.span_ns(pt.INGEST_SPAN)
        assert len(ingests) == 11


def test_the_two_file_formats_read_alike():
    a, b = pt.load(SCOPED["solve"]), pt.load(SCOPED_XPLANE)
    assert a.window() == pytest.approx(b.window(), abs=1e3)
    assert [len(ops) for ops in a.device_ops] == \
        [len(ops) for ops in b.device_ops]
    assert [op for op, _, _ in a.device_ops[0]] == \
        [op for op, _, _ in b.device_ops[0]]
    assert sorted(n for n, *_ in a.spans) == sorted(n for n, *_ in b.spans)


def test_a_stale_trace_directory_is_refused(tmp_path, monkeypatch):
    """A newer ``bench-trace-*`` directory whose window is not the run's
    is passed over; with none that matches, nothing is read."""
    run = _run_on(tmp_path, monkeypatch, SCOPED["solve"], {"sweeps": 3})
    stale = tmp_path / "bench-trace-stale" / "plugins" / "profile" / "t"
    stale.mkdir(parents=True)
    shutil.copy(UNSCOPED, stale / "host.xplane.pb")
    later = os.path.getmtime(tmp_path / "bench-trace-run") + 60
    os.utime(tmp_path / "bench-trace-stale", (later, later))
    assert _reader("binning_ms.solve")(run) is not None

    shutil.rmtree(tmp_path / "bench-trace-run")
    run = harness.Run(cell=None, counters={"sweeps": 3}, trace=run.trace,
                      device_kind="TPU v5 lite")
    assert pt.for_run(run) is None
    assert _reader("binning_ms.solve")(run) is None


def test_span_names_and_arguments():
    assert pt.split_span_name("repro.ingest#batch=3#") == (
        "repro.ingest", {"batch": "3"})
    assert pt.split_span_name("window") == ("window", {})
    assert pt.scopes_of("jit(f)/while/body/mm_sweep/binning/pad:") == (
        "jit(f)", "while", "body", "mm_sweep", "binning")


def test_program_names_stay_apart_from_the_benchmark_spans():
    """The benchmark's reduction reads its own spans by name; no program
    span may be taken for one.  The names this file reads are the
    program's."""
    from repro import tracing

    assert not set(tracing.HOST_SPANS) & set(tr.SPANS)
    assert pt.DEVICE_SCOPES == tracing.DEVICE_SCOPES
    assert pt.INGEST_SPAN == tracing.INGEST


def test_a_traced_tiny_stream_gives_the_host_span_metric():
    cell = tiny_cell("graph500-s20.stream-b16")
    d = tempfile.mkdtemp(prefix=pt.TRACE_DIR_PREFIX)
    try:
        line, _ = run_tiny(cell, trace_dir=d)
    finally:
        shutil.rmtree(d, ignore_errors=True)
    assert line["correct"]
    value = line["metrics"]["ingest_host_ms.stream"]["value"]
    assert 0 < value < 1e4
