"""Read the program's own scopes and spans from a traced run.

The program names its layers (``src/repro/tracing.py``): device scopes
(``jax.named_scope``) sit in each device operation's name path, the
``tf_op`` of its trace event (``jit(contour_labels)/while/body/mm_sweep/
binning/gather:``), and host spans (``jax.profiler.TraceAnnotation``,
named ``repro.*``) sit on the host plane, on the device's clock.
``bench/trace.py`` reads neither; this module does, for the per-layer
readers of ``bench/metrics/`` that split a layer's time by scope.

What it reads: the ``<host>.trace.json.gz`` that ``stop_trace`` writes
beside the ``.xplane.pb``, whose device events carry ``tf_op``; where a
run lacks it, the ``.xplane.pb`` itself, decoded with the protobuf wire
format (the stat is in ``XPlane.event_metadata[].stats``, named through
``stat_metadata``), which ``jax.profiler.ProfileData`` does not expose.

Where it finds the file: :class:`bench.harness.Run` carries no path, so
:func:`for_run` looks in the ``bench-trace-*`` directories under
``tempfile.gettempdir()`` (the prefix ``bench/run.py`` gives its trace
directory), newest first, and takes a file only where its ``window``
span has the length of ``run.trace.window()``.  The result is cached on
the run.

A program that names no scope, as before it did, gives traces in which
no operation carries one: every reader then returns None, never 0.
"""
from __future__ import annotations

import dataclasses
import glob
import gzip
import json
import os
import struct
import tempfile
from typing import Iterable, Optional

import numpy as np

from bench import trace as tr

TRACE_DIR_PREFIX = "bench-trace-"
# the program's host spans begin with this (repro.tracing)
PROGRAM_SPAN_PREFIX = "repro."
# a trace file is the run's own when its window lasts as long as the
# run's, to this many nanoseconds (the two files round differently)
WINDOW_MATCH_NS = 1000.0

# The program's device scopes (repro.tracing.DEVICE_SCOPES), named here
# so that this file reads a program that lacks them.
MM_SWEEP = "mm_sweep"
BINNING = "binning"
SCATTER_KERNEL = "scatter_kernel"
POINTER_JUMP = "pointer_jump"
CONVERGE_CHECK = "converge_check"
COMPACT = "compact"
COMPRESS = "compress"
DEVICE_SCOPES = (MM_SWEEP, "update_stream", BINNING, SCATTER_KERNEL,
                 "fused_relax", "scatter_min", POINTER_JUMP, CONVERGE_CHECK,
                 COMPACT, COMPRESS, "supervertex_rewrite", "sampling_prep",
                 "pad", "store", "label_allreduce")
INGEST_SPAN = "repro.ingest"


def scopes_of(tf_op: str) -> tuple:
    """The name path of an op, without its last part (the primitive, as
    in ``.../binning/pad``, which is no scope): ``"jit(f)/while/body/
    mm_sweep/binning/gather:"`` gives ``("jit(f)", "while", "body",
    "mm_sweep", "binning")``."""
    path = tf_op.rsplit(":", 1)[0] if ":" in tf_op else tf_op
    return tuple(path.split("/")[:-1])


def split_span_name(name: str) -> tuple:
    """``"repro.ingest#batch=3#"`` -> ``("repro.ingest", {"batch": "3"})``:
    a ``TraceAnnotation``'s arguments, where the trace keeps them in the
    name."""
    base, _, rest = name.partition("#")
    args = {}
    for part in rest.split("#"):
        if "=" in part:
            k, _, v = part.partition("=")
            args[k] = v
    return base, args


@dataclasses.dataclass
class ProgramTrace:
    """Leaf device operations with their name paths, and host spans."""

    device_ops: list    # per chip: leaf ops (tf_op, start_ns, end_ns)
    spans: list         # host spans (name, args, start_ns, end_ns)

    def window(self) -> Optional[tuple]:
        """``(start_ns, end_ns)`` of the ``window`` span, or None."""
        w = [(s, e) for n, _, s, e in self.spans if n == "window"]
        return (min(s for s, _ in w), max(e for _, e in w)) if w else None

    def intervals(self, names: Iterable[str]) -> np.ndarray:
        names = set(names)
        return tr.merge([(s, e) for n, _, s, e in self.spans if n in names])

    def scope_ns(self, scopes: Iterable[str],
                 within: Iterable[str]) -> Optional[float]:
        """Device time of the leaf ops under any of ``scopes`` inside the
        host spans named ``within``, averaged over the chips; None where
        no op of the window carries any of them."""
        scopes = set(scopes)
        spans = self.intervals(within)
        lo, hi = self.window() or (0.0, 0.0)
        found, per_chip = False, []
        for ops in self.device_ops:
            mine = [(s, e) for op, s, e in ops
                    if scopes.intersection(scopes_of(op))
                    and e > lo and s < hi]
            found = found or bool(mine)
            busy = tr.merge(mine)
            per_chip.append(sum(tr.covered(busy, a, b) for a, b in spans))
        return float(np.mean(per_chip)) if found else None

    def span_ns(self, name: str) -> list:
        """Lengths of the host spans named ``name`` inside the window."""
        lo, hi = self.window() or (0.0, 0.0)
        return [e - s for n, _, s, e in self.spans
                if n == name and s >= lo and e <= hi]

    def leaf_ns_by(self, key) -> dict:
        """Leaf device time in the window, summed by ``key(tf_op)`` and
        averaged over the chips."""
        lo, hi = self.window() or (0.0, 0.0)
        out: dict = {}
        for ops in self.device_ops:
            for op, s, e in ops:
                d = min(e, hi) - max(s, lo)
                if d > 0:
                    k = key(op)
                    out[k] = out.get(k, 0.0) + d
        n = max(len(self.device_ops), 1)
        return {k: v / n for k, v in out.items()}

    def scoped_share(self, scopes: Iterable[str] = DEVICE_SCOPES) -> float:
        """Share of leaf device time in the window whose op lies under one
        of ``scopes``."""
        scopes = set(scopes)
        t = self.leaf_ns_by(lambda op: bool(scopes.intersection(
            scopes_of(op))))
        total = sum(t.values())
        return t.get(True, 0.0) / total if total else 0.0


# -- the two file formats ------------------------------------------------

def load(path: str) -> ProgramTrace:
    """Read a ``.trace.json.gz`` or an ``.xplane.pb`` file."""
    if path.endswith(".json.gz"):
        return _load_json(path)
    with open(path, "rb") as f:
        return _load_xplane(f.read())


def leaves(ops, eps_ns: float = 1.0) -> list:
    """The operations that hold no other, as ``bench.trace.leaves`` has
    them, but robust to what that rule trips on: an op of no length that
    starts with another makes the other look like a loop that holds it
    (Delaunay's 108 ms binning fusion, PERF.md section 5), and ends read
    from microseconds with three decimals differ from the nanosecond ones
    by rounding.  So ops of no length are dropped (they hold no time) and
    ends are compared to within ``eps_ns``."""
    ops = sorted((op for op in ops if op[2] > op[1]),
                 key=lambda op: (op[1], -op[2]))
    return [op for op, nxt in zip(ops, ops[1:] + [None])
            if nxt is None or nxt[1] >= op[2] - eps_ns
            or nxt[2] > op[2] + eps_ns]


def _program_trace(device_ops: list, spans: list) -> ProgramTrace:
    return ProgramTrace(device_ops=[leaves(ops) for ops in device_ops],
                        spans=spans)


def _load_json(path: str) -> ProgramTrace:
    with gzip.open(path, "rt") as f:
        events = json.load(f)["traceEvents"]
    procs, threads = {}, {}
    for ev in events:
        if ev.get("ph") == "M" and ev.get("name") == "process_name":
            procs[ev["pid"]] = ev["args"]["name"]
        elif ev.get("ph") == "M" and ev.get("name") == "thread_name":
            threads[(ev["pid"], ev["tid"])] = ev["args"]["name"]
    ops: dict = {}
    spans = []
    for ev in events:
        if ev.get("ph") != "X":
            continue
        proc = procs.get(ev["pid"], "")
        s = float(ev["ts"]) * 1e3
        e = s + float(ev.get("dur", 0.0)) * 1e3
        if proc.startswith("/device:") and \
                threads.get((ev["pid"], ev["tid"])) == tr.DEVICE_OPS_LINE:
            ops.setdefault(ev["pid"], []).append(
                (ev.get("args", {}).get("tf_op", ""), s, e))
        elif proc.startswith("/host:CPU"):
            name, args = split_span_name(ev["name"])
            if name in tr.SPANS or name.startswith(PROGRAM_SPAN_PREFIX):
                args.update({k: str(v) for k, v in
                             ev.get("args", {}).items()})
                spans.append((name, args, s, e))
    return _program_trace([ops[p] for p in sorted(ops)], spans)


def _varint(b, i: int) -> tuple:
    shift = out = 0
    while True:
        c = b[i]
        i += 1
        out |= (c & 0x7F) << shift
        shift += 7
        if c < 0x80:
            return out, i


def _fields(b) -> list:
    """``[(field number, value)]`` of one protobuf message: an int for a
    varint or fixed field, a memoryview for a length-delimited one."""
    out, i, n = [], 0, len(b)
    while i < n:
        key, i = _varint(b, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(b, i)
        elif wire == 1:
            v = struct.unpack_from("<Q", b, i)[0]
            i += 8
        elif wire == 2:
            size, i = _varint(b, i)
            v = b[i:i + size]
            i += size
        elif wire == 5:
            v = struct.unpack_from("<I", b, i)[0]
            i += 4
        else:
            raise ValueError(f"protobuf wire type {wire} in an XSpace")
        out.append((key >> 3, v))
    return out


def _text(v) -> str:
    return bytes(v).decode("utf-8", "replace")


def _map_entries(entries) -> dict:
    """A protobuf ``map<int64, Message>`` field: key 1, value 2."""
    out = {}
    for raw in entries:
        f = dict(_fields(raw))
        out[f.get(1, 0)] = f.get(2, b"")
    return out


def _stats(raw_stats, stat_names: dict) -> dict:
    """``XStat``s as ``{name: value}``; a string stat may be interned
    (``ref_value``: the id of a stat metadata whose name is the text)."""
    out = {}
    for raw in raw_stats:
        f = dict(_fields(raw))
        name = stat_names.get(f.get(1), "")
        if 5 in f:
            out[name] = _text(f[5])
        elif 7 in f:
            out[name] = stat_names.get(f[7], "")
        elif 4 in f:
            out[name] = str(f[4] - (1 << 64) if f[4] >> 63 else f[4])
        elif 3 in f:
            out[name] = str(f[3])
    return out


def _load_xplane(data: bytes) -> ProgramTrace:
    """Device ops with ``tf_op`` and host spans from an ``XSpace``
    (``tsl/profiler/protobuf/xplane.proto``): XSpace.planes = 1;
    XPlane name = 2, lines = 3, event_metadata = 4, stat_metadata = 5;
    XLine name = 2, timestamp_ns = 3, events = 4; XEvent metadata_id = 1,
    offset_ps = 2, duration_ps = 3, stats = 4; XEventMetadata name = 2,
    stats = 5; XStatMetadata name = 2; XStat metadata_id = 1,
    int64 = 4, str = 5, ref = 7."""
    device_ops, spans = [], []
    for num, raw_plane in _fields(memoryview(data)):
        if num != 1:
            continue
        plane = _fields(raw_plane)
        name = next((_text(v) for f, v in plane if f == 2), "")
        device = name.startswith("/device:")
        if not device and not name.startswith("/host:CPU"):
            continue
        stat_names = {k: _text(dict(_fields(v)).get(2, b""))
                      for k, v in _map_entries(
                          v for f, v in plane if f == 5).items()}
        meta = {}
        for k, v in _map_entries(v for f, v in plane if f == 4).items():
            fm = _fields(v)
            meta[k] = (next((_text(x) for f, x in fm if f == 2), ""),
                       [x for f, x in fm if f == 5])
        op_of = {}
        ops = None
        for f, raw_line in plane:
            if f != 3:
                continue
            line = _fields(raw_line)
            lname = next((_text(v) for g, v in line if g == 2), "")
            if device:
                if lname != tr.DEVICE_OPS_LINE:
                    continue
                ops = []
                device_ops.append(ops)
            t0 = next((v for g, v in line if g == 3), 0)
            for g, raw_ev in line:
                if g != 4:
                    continue
                ev = _fields(raw_ev)
                fe = dict(ev)
                s = t0 + fe.get(2, 0) / 1e3
                e = s + fe.get(3, 0) / 1e3
                mid = fe.get(1, 0)
                if device:
                    if mid not in op_of:
                        op_of[mid] = _stats(meta.get(mid, ("", []))[1],
                                            stat_names).get("tf_op", "")
                    ops.append((op_of[mid], s, e))
                    continue
                ename, args = split_span_name(meta.get(mid, ("", []))[0])
                if ename in tr.SPANS or \
                        ename.startswith(PROGRAM_SPAN_PREFIX):
                    args.update(_stats([v for h, v in ev if h == 4],
                                       stat_names))
                    spans.append((ename, args, s, e))
    return _program_trace(device_ops, spans)


# -- finding a run's own file --------------------------------------------

def trace_file(log_dir: str) -> Optional[str]:
    """The newest ``.trace.json.gz`` under a profiler log dir, else its
    newest ``.xplane.pb``, else None."""
    for pattern in ("*.trace.json.gz", "*.xplane.pb"):
        files = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                       pattern))
        if files:
            return max(files, key=os.path.getmtime)
    return None


def find(window_ns: float) -> Optional[ProgramTrace]:
    """The trace under the newest ``bench-trace-*`` directory of the temp
    dir whose ``window`` lasts ``window_ns``."""
    dirs = sorted(glob.glob(os.path.join(tempfile.gettempdir(),
                                         TRACE_DIR_PREFIX + "*")),
                  key=os.path.getmtime, reverse=True)
    for d in dirs:
        path = trace_file(d)
        if path is None:
            continue
        pt = load(path)
        w = pt.window()
        if w is not None and abs((w[1] - w[0]) - window_ns) <= \
                WINDOW_MATCH_NS:
            return pt
    return None


def for_run(run) -> Optional[ProgramTrace]:
    """The program trace of a traced run, found once and cached on it."""
    if run.trace is None:
        return None
    if not hasattr(run, "program_trace"):
        lo, hi = run.trace.window()
        run.program_trace = find(hi - lo)
    return run.program_trace


def scope_ms_per(run, scopes, within, per: str) -> Optional[float]:
    """Device ms of ``scopes`` inside the spans ``within``, per
    ``run.counters[per]`` (sweeps, batches); None where the program names
    no such scope or the counter is missing."""
    n = run.counters.get(per)
    pt = for_run(run) if n else None
    ns = pt.scope_ns(scopes, within) if pt is not None else None
    return None if ns is None else ns / 1e6 / n
