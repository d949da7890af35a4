"""``correct`` comes out false when the timed path is broken underneath.

Each cell is driven below the harness's look for a chip, on the CPU at a
small size, with one fault planted in the program's place: a step that
returns its state unchanged, half of each batch left out, and an answer
altered where it is produced.  These cells run on one chip and have no
exchange between chips to leave out; a cell of four chips is broken so
in ``test_bench_mesh.py``, on four CPU devices.  The control (each
loop's ``control()``, read on the chip by ``bench/control.py``) must
fail too, and a sound run must pass.
"""
import dataclasses

import jax.numpy as jnp
import pytest

import repro
from bench.tests.conftest import run_tiny, tiny_cell

SOLVE_CELLS = ["graph500-s20.solve", "delaunay-n20.solve"]
STREAM_CELL = "graph500-s20.stream-b16"


def _state_unchanged(real, g, **kw):
    res = real(g, **kw)
    return dataclasses.replace(res, labels=jnp.arange(g.n_vertices))


def _half_the_edges(real, g, **kw):
    half = g.n_edges // 2
    return real(repro.Graph(src=g.src[:half], dst=g.dst[:half],
                            n_vertices=g.n_vertices), **kw)


def _answer_altered(real, g, **kw):
    res = real(g, **kw)
    return dataclasses.replace(res, labels=res.labels.at[0].add(1))


SOLVE_FAULTS = [_state_unchanged, _half_the_edges, _answer_altered]


@pytest.mark.parametrize("name", SOLVE_CELLS + [STREAM_CELL])
def test_a_sound_run_is_correct(name):
    line, checks = run_tiny(tiny_cell(name))
    assert line["correct"] and line["failed"] == 0
    assert checks == {"mismatched_vertices": (0, 0)}
    assert list(line)[-1] == "checks"


@pytest.mark.parametrize("name", SOLVE_CELLS)
@pytest.mark.parametrize("fault", SOLVE_FAULTS, ids=lambda f: f.__name__)
def test_a_broken_solve_is_not_correct(monkeypatch, name, fault):
    real = repro.solve
    monkeypatch.setattr(repro, "solve",
                        lambda g, *a, **kw: fault(real, g, *a, **kw))
    line, checks = run_tiny(tiny_cell(name))
    assert not line["correct"]
    assert checks["mismatched_vertices"][0] > 0


class _Unchanged(repro.StreamingConnectivity):
    def ingest(self, src, dst, **kw):
        return self


class _HalfBatch(repro.StreamingConnectivity):
    def ingest(self, src, dst, **kw):
        return super().ingest(src[:len(src) // 2], dst[:len(dst) // 2], **kw)


class _Altered(repro.StreamingConnectivity):
    @property
    def labels(self):
        return super().labels.at[0].add(1)


@pytest.mark.parametrize("fault", [_Unchanged, _HalfBatch, _Altered],
                         ids=lambda f: f.__name__)
def test_a_broken_stream_is_not_correct(monkeypatch, fault):
    monkeypatch.setattr(repro, "StreamingConnectivity", fault)
    cell = tiny_cell(STREAM_CELL)
    # a sparse warm start, so that batches join components
    cell.traffic["warm_start_fraction"] = 0.05
    line, checks = run_tiny(cell)
    assert not line["correct"]
    assert checks["mismatched_vertices"][0] > 0


@pytest.mark.parametrize("name", SOLVE_CELLS + [STREAM_CELL])
def test_the_control_is_not_correct(name):
    cell = tiny_cell(name)
    with cell.loop.control():
        line, checks = run_tiny(cell)
    assert not line["correct"]
    assert checks["mismatched_vertices"][0] > 0
