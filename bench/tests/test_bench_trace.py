"""The reduction from a profiler trace to busy time, idle gaps and ops."""
import os

import numpy as np
import pytest

from bench import trace as tr

FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures",
                       "solve_v5e.xplane.pb")


def test_merge_joins_overlapping_and_touching_intervals():
    got = tr.merge([(5, 7), (0, 2), (1, 3), (3, 4), (6, 9), (10, 11)])
    np.testing.assert_array_equal(got, [[0, 4], [5, 9], [10, 11]])
    assert tr.merge([]).shape == (0, 2)


def test_covered_and_holes_clip_to_the_interval():
    busy = tr.merge([(0, 2), (4, 6), (8, 12)])
    assert tr.covered(busy, 1, 9) == 1 + 2 + 1
    assert tr.covered(busy, 2, 4) == 0
    assert tr.holes(busy, 1, 10) == [(2, 4), (6, 8)]
    assert tr.holes(busy, -1, 14) == [(-1, 0), (2, 4), (6, 8), (12, 14)]


def _synthetic():
    # two chips; window [0, 100) ns; host spans label the gaps; on chip 0
    # a loop holds two operations with a gap between them
    ops = [[("while.0", 0, 40), ("fusion.1", 0, 25), ("sort.2", 30, 40),
            ("fusion.1", 70, 80)],
           [("fusion.1", 10, 20), ("custom-call.3", 60, 100)]]
    spans = [("window", 0, 100), ("solve", 0, 45), ("labels_to_host", 45, 60),
             ("ingest", 60, 100)]
    return tr.Trace(device_ops=ops, spans=spans)


def test_busy_idle_ops_and_gaps_of_a_synthetic_trace():
    t = _synthetic()
    assert t.window() == (0, 100)
    # chip 0 busy 25 + 10 + 10 = 45 (the loop's own event spans a gap),
    # chip 1 busy 10 + 40 = 50
    assert t.busy_ns() == (45 + 50) / 2
    assert t.busy_ns(["solve"]) == (35 + 10) / 2
    assert t.busy_ns(["ingest"]) == (10 + 40) / 2
    assert t.ops_by_name() == [["fusion.1", 22.5 / 1e9],
                               ["custom-call.3", 20 / 1e9],
                               ["sort.2", 5 / 1e9]]
    gaps = t.idle_gaps()
    # chip 0: [25,30) inside the loop, in solve; [40,70) mostly
    # labels_to_host; [80,100) ingest; chip 1: [0,10) solve, [20,60)
    # mostly solve
    assert gaps == [["solve", 40 / 1e9], ["labels_to_host", 30 / 1e9],
                    ["ingest", 20 / 1e9], ["solve", 10 / 1e9],
                    ["solve", 5 / 1e9]]


def test_leaves_leave_out_what_holds_other_operations():
    ops = [("a", 5, 15), ("loop", 0, 40), ("b", 0, 10), ("c", 20, 40),
           ("d", 40, 50), ("e", 45, 60)]
    # a and b overlap without one holding the other; so do d and e
    assert [op[0] for op in tr.leaves(ops)] == ["b", "a", "c", "d", "e"]


def test_op_label_keeps_opcode_instruction_and_type():
    name = ("%fusion.84 = s32[67108864]{0:T(1024)} fusion(s32[67108864]"
            "{0:T(1024)} %a, s32[67108864]{0:T(1024)} %b), kind=kCustom")
    assert tr.op_label(name) == "fusion fusion.84 s32[67108864]"
    loop = ("%while.43 = (s32[8192]{0:T(1024)S(1)}, pred[]{:T(512)}) "
            "while((s32[8192]{0:T(1024)S(1)}, pred[]{:T(512)}) %tuple.54)")
    assert tr.op_label(loop) == "while while.43 (s32[8192], pred[])"


def test_a_trace_without_a_window_span_is_an_error():
    with pytest.raises(ValueError):
        tr.Trace(device_ops=[], spans=[("solve", 0, 1)]).window()


def test_recorded_v5e_trace():
    """A traced solve window recorded on a TPU v5e: device ops on the
    chip's ``XLA Ops`` line, the benchmark's spans on the host, one
    clock."""
    t = tr.load(FIXTURE)
    assert len(t.device_ops) == 1 and len(t.device_ops[0]) > 0
    assert {"window", "solve", "labels_to_host"} <= {s[0] for s in t.spans}
    lo, hi = t.window()
    busy = t.busy_ns()
    assert 0 < busy <= hi - lo
    # the solve's while loops hold its operations; busy time counts the
    # operations, not the loops' own events
    loops = [op for op in t.device_ops[0] if " while(" in op[0]]
    assert loops and not set(loops) & set(tr.leaves(t.device_ops[0]))
    union = tr.covered(tr.merge([(s, e) for _, s, e in t.device_ops[0]]),
                       lo, hi)
    assert busy < union
    # every device op of the solves lies inside the spans that call them
    in_calls = t.busy_ns(["solve", "labels_to_host"])
    assert in_calls == pytest.approx(busy, rel=0.05)
    ops = t.ops_by_name()
    assert 0 < len(ops) <= 10
    assert [s for _, s in ops] == sorted((s for _, s in ops), reverse=True)
    gaps = t.idle_gaps()
    assert all(name in tr.SPANS + (tr.NO_SPAN,) for name, _ in gaps)
    assert sum(s for _, s in gaps) <= (hi - lo - busy) / 1e9 + 1e-9
