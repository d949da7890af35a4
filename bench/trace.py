"""Reduce a JAX profiler trace to device busy time, idle gaps and ops.

A traced run records its window with ``jax.profiler`` together with the
benchmark's own host spans (``jax.profiler.TraceAnnotation`` around each
call into the program, see ``SPANS``).  This module reads the
``.xplane.pb`` file the profiler writes, with nothing but JAX and NumPy,
and reduces it:

* device operations: the events of the ``XLA Ops`` line of each device
  plane (one plane per chip), as ``(name, start_ns, end_ns)``;
* host spans: the events of the host plane whose name is one of
  ``SPANS``;
* busy time: the length of the union of a chip's leaf operations (a
  loop's own event spans its body and every gap in it, so it is left
  out), clipped to a set of spans, averaged over the chips;
* idle gaps: the holes in that union inside the ``window`` span, each
  labelled by the host span that overlaps it most.

The per-layer metric readers under ``bench/metrics/`` take their numbers
from a :class:`Trace`; ``bench/run.py`` takes ``busy_s``, ``window_s``
and the ``breakdown`` from it.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Iterable, Optional

import numpy as np

# Host spans the benchmark records.  "window" wraps the measured window.
SPANS = ("window", "warmup", "draw", "solve", "labels_to_host", "ingest",
         "reference")
DEVICE_OPS_LINE = "XLA Ops"
NO_SPAN = "no span"


@dataclasses.dataclass
class Trace:
    """Device operations per chip and the benchmark's host spans."""

    device_ops: list          # per chip: list of (name, start_ns, end_ns)
    spans: list               # (name, start_ns, end_ns)

    def intervals(self, names: Iterable[str]) -> np.ndarray:
        """Merged ``[start, end)`` intervals of the spans named."""
        names = set(names)
        return merge([(s, e) for n, s, e in self.spans if n in names])

    def window(self) -> tuple:
        """``(start_ns, end_ns)`` of the measured window."""
        w = self.intervals(["window"])
        if not len(w):
            raise ValueError("trace holds no 'window' span")
        return float(w[0, 0]), float(w[-1, 1])

    def busy_ns(self, span_names: Optional[Iterable[str]] = None) -> float:
        """Device busy time inside the spans named (default: the window),
        averaged over the chips."""
        spans = self.intervals(span_names or ["window"])
        per_chip = []
        for ops in self.device_ops:
            busy = merge([(s, e) for _, s, e in leaves(ops)])
            per_chip.append(sum(covered(busy, lo, hi) for lo, hi in spans))
        return float(np.mean(per_chip)) if per_chip else 0.0

    def ops_by_name(self, top: int = 10) -> list:
        """``[name, seconds]`` of the device operations that took most
        time inside the window, summed over their runs and averaged over
        the chips.  A control-flow operation (a ``while`` loop) that holds
        others is left out for the operations it holds."""
        lo, hi = self.window()
        total: dict = {}
        for ops in self.device_ops:
            for name, s, e in leaves(ops):
                d = min(e, hi) - max(s, lo)
                if d > 0:
                    total[name] = total.get(name, 0.0) + d
        n = max(len(self.device_ops), 1)
        ranked = sorted(total.items(), key=lambda kv: -kv[1])[:top]
        return [[op_label(name), ns / n / 1e9] for name, ns in ranked]

    def idle_gaps(self, top: int = 10) -> list:
        """``[label, seconds]`` of the longest device idle gaps inside the
        window, on any chip, each labelled by the host span (other than
        the window) that overlaps it most."""
        lo, hi = self.window()
        gaps = []
        for ops in self.device_ops:
            gaps.extend(holes(merge([(s, e) for _, s, e in leaves(ops)]),
                              lo, hi))
        gaps.sort(key=lambda g: g[0] - g[1])
        labelled = [(n, s, e) for n, s, e in self.spans if n != "window"]
        out = []
        for g_lo, g_hi in gaps[:top]:
            best, best_ns = NO_SPAN, 0.0
            for name, s, e in labelled:
                ov = min(e, g_hi) - max(s, g_lo)
                if ov > best_ns:
                    best, best_ns = name, ov
            out.append([best, float(g_hi - g_lo) / 1e9])
        return out


def leaves(ops) -> list:
    """The operations that hold no other: on a device's op line, a loop's
    body runs inside the loop's own interval.  An operation holds another
    where the next to start (ties: the longer first) ends inside it."""
    ops = sorted(ops, key=lambda op: (op[1], -op[2]))
    return [op for op, nxt in zip(ops, ops[1:] + [None])
            if nxt is None or nxt[1] >= op[2] or nxt[2] > op[2]]


def op_label(name: str, width: int = 100) -> str:
    """A short label for an HLO operation's trace name:
    ``"<opcode> <instruction> <result type>"``, without layouts."""
    instr, _, rest = name.partition(" = ")
    rest = re.sub(r"\{[^{}]*\}", "", rest)
    if rest.startswith("("):          # a tuple type: up to its closing ")"
        depth = 0
        for j, ch in enumerate(rest):
            depth += {"(": 1, ")": -1}.get(ch, 0)
            if depth == 0:
                break
        rtype, after = rest[:j + 1], rest[j + 1:].lstrip()
    else:
        rtype, _, after = rest.partition(" ")
    opcode = after.split("(", 1)[0]
    return f"{opcode} {instr.lstrip('%')} {rtype}".strip()[:width]


def merge(intervals) -> np.ndarray:
    """Sorted, disjoint ``(k, 2)`` union of ``[start, end)`` intervals."""
    if not len(intervals):
        return np.zeros((0, 2))
    a = np.asarray(intervals, dtype=np.float64)
    a = a[np.argsort(a[:, 0], kind="stable")]
    # an interval starts a new run where it begins after every earlier end
    run_end = np.maximum.accumulate(a[:, 1])
    new = np.ones(len(a), bool)
    new[1:] = a[1:, 0] > run_end[:-1]
    starts = a[new, 0]
    ends = np.maximum.reduceat(a[:, 1], np.flatnonzero(new))
    return np.stack([starts, ends], axis=1)


def covered(merged: np.ndarray, lo: float, hi: float) -> float:
    """Length of ``merged`` (from :func:`merge`) inside ``[lo, hi)``."""
    if not len(merged) or hi <= lo:
        return 0.0
    i0 = np.searchsorted(merged[:, 1], lo, side="right")
    i1 = np.searchsorted(merged[:, 0], hi, side="left")
    seg = merged[i0:i1]
    return float(np.sum(np.clip(seg[:, 1], lo, hi)
                        - np.clip(seg[:, 0], lo, hi)))


def holes(merged: np.ndarray, lo: float, hi: float) -> list:
    """The gaps of ``[lo, hi)`` that ``merged`` does not cover."""
    out, cur = [], lo
    for s, e in merged:
        if e <= cur:
            continue
        if s >= hi:
            break
        if s > cur:
            out.append((cur, s))
        cur = max(cur, e)
    if cur < hi:
        out.append((cur, hi))
    return out


def find_xplane(log_dir: str) -> str:
    """The newest ``.xplane.pb`` file the profiler wrote under a dir."""
    files = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return max(files, key=os.path.getmtime)


def load(path: str) -> Trace:
    """Read a profiler trace (an ``.xplane.pb`` file or the log dir)."""
    import jax

    if os.path.isdir(path):
        path = find_xplane(path)
    data = jax.profiler.ProfileData.from_file(path)
    device_ops, spans = [], []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name == DEVICE_OPS_LINE:
                    device_ops.append([
                        (ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                        for ev in line.events])
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                spans.extend(
                    (ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                    for ev in line.events if ev.name in SPANS)
    return Trace(device_ops=device_ops, spans=spans)
