"""What every traffic loop shares: logging, spans and provenance readings.

A traffic file (``bench/traffic/<name>.json``) holds parameters and names
its ``loop``, a file of its own, ``bench/loops/<loop>.py``, which the
harness finds by that name.  A loop module offers:

* ``Loop(cell, seed, src, dst, n)``: built on a drawn graph, it warms up
  every shape its window uses;
* ``Loop.window(seconds)``: drives the program for ``seconds`` and
  returns the counters the end-to-end and per-layer metrics are read
  from (at least ``ops``, ``failed`` and ``window_s``);
* ``Loop.check()``: frees the program's state, runs the plain reference
  on the host, and returns the numbers compared, each with its limit;
* ``control()``: a context manager that puts the control of ``correct``
  in the program's place (``bench/control.py``).

Loops reach the program only through its public entry points, looked up
on ``repro`` at call time (``repro.solve``, ``repro.StreamingConnectivity``)
with default options, so that the plan the planner picks is part of what
is measured.  A loop of a cell on several chips passes ``mesh=mesh_of(cell)``
and places its drawn edges on that mesh once, at set-up, with
``place_edges``, so that its window moves no edge between chips.
"""
from __future__ import annotations

import sys

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

# the mesh axis a cell's edge list is split along
EDGE_AXIS = "data"

span = jax.profiler.TraceAnnotation


def log(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr, flush=True)


def fallbacks(provenance) -> list:
    """Provenance entries that show a fallback off the planned path."""
    return [p for p in (provenance or ())
            if p.startswith("kernel_fallback:") or "origin=fallback" in p]


def plans(provenance) -> list:
    """The ``plan:`` entries of a provenance record."""
    return [p for p in (provenance or ()) if p.startswith("plan:")]


def next_pow2(x: int) -> int:
    return 1 << max(0, int(x) - 1).bit_length()


def half_the_sweeps(real):
    """``solve`` with its ``max_iters`` budget cut to half the sweeps (at
    least one) that the sound solve of the same graph takes: the control
    of every loop that calls ``solve``.

    One sweep short is not enough: the last sweep often only compresses
    label chains, which the solver's final pointer jump does anyway, and
    the labels come out exact.
    """
    sweeps: dict = {}

    def solve(graph, *args, **kwargs):
        if id(graph) not in sweeps:
            sweeps[id(graph)] = int(real(graph, *args, **kwargs).iterations)
        return real(graph, *args, max_iters=max(sweeps[id(graph)] // 2, 1),
                    **kwargs)

    return solve


def mesh_of(cell) -> jax.sharding.Mesh:
    """A one-axis ``("data",)`` mesh of the first ``cell.chips`` devices."""
    devices = jax.devices()[:cell.chips]
    if len(devices) < cell.chips:
        raise RuntimeError(f"{cell.name} needs {cell.chips} devices; JAX "
                           f"found {len(devices)}")
    return jax.sharding.Mesh(np.array(devices), (EDGE_AXIS,),
                             axis_types=(jax.sharding.AxisType.Auto,))


def place_edges(mesh: jax.sharding.Mesh, src, dst):
    """``(src, dst)`` split into equal contiguous blocks over ``mesh``'s
    edge axis, one per chip, as ``solve(graph, mesh=mesh)`` shards them."""
    chips = mesh.shape[EDGE_AXIS]
    if src.shape[0] % chips:
        raise ValueError(f"{src.shape[0]} edges do not split evenly over "
                         f"{chips} chips")
    out = jax.device_put((src, dst), NamedSharding(mesh, P(EDGE_AXIS)))
    return jax.block_until_ready(out)
