"""Peaks by device kind, and the bytes an MM sweep has to move.

The peaks live in ``bench/peaks.json``, keyed by JAX's ``device_kind``.
A device that is not in the table is an error, never a default.
"""
from __future__ import annotations

import json
import os

PEAKS_FILE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "peaks.json")


def peak(device_kind: str, key: str, path: str = PEAKS_FILE) -> float:
    """The published peak ``key`` of one chip of ``device_kind``."""
    with open(path) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{path}; add its published numbers")
    return float(table[device_kind][key])


def sweep_bytes(n_vertices: int, n_edges: int) -> int:
    """Least HBM bytes of one order-2 minimum-mapping sweep (Contour C-2).

    Per edge ``(w, v)`` the MM^2 operator (paper Definition 3) reads the
    edge, 2 int32 ids (8 B); gathers ``L[w]``, ``L[v]``, ``L[L[w]]`` and
    ``L[L[v]]``, 4 labels of 4 B (16 B); and scatter-mins
    ``z = min(L[L[w]], L[L[v]])`` into ``w``, ``v``, ``L[w]`` and ``L[v]``,
    4 writes of 4 B (16 B): 40 B per edge.  The label array is read once
    and written once per sweep, 8 B per vertex.  So ``40 m + 8 n`` bytes,
    whatever backend realises the sweep; binning, sorting, padding, the
    pointer jump and the convergence test are work beyond this floor.

    The count assumes the labels live in HBM, as they do at n = 2^20 on
    every backend the program has; a sweep that keeps all of L on chip
    needs a new count.
    """
    return 40 * int(n_edges) + 8 * int(n_vertices)
