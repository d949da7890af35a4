"""The MM sweep's byte count, the peaks table and the roofline reader."""
import pytest

from bench import harness, roofline
from bench import trace as tr
from bench.tests.conftest import ROOT


def test_sweep_bytes_against_a_hand_count():
    # 2 edges on 3 vertices.  Per edge (w, v): read w and v (2 x 4 B);
    # gather L[w], L[v], L[L[w]], L[L[v]] (4 x 4 B); scatter-min into w,
    # v, L[w], L[v] (4 x 4 B).  Per vertex: read and write L (2 x 4 B).
    per_edge = 2 * 4 + 4 * 4 + 4 * 4
    assert roofline.sweep_bytes(3, 2) == 2 * per_edge + 3 * 2 * 4 == 104
    # graph500-s20: 680 MB a sweep
    assert roofline.sweep_bytes(1 << 20, 1 << 24) == 679_477_248


def test_peaks_are_keyed_by_device_kind():
    assert roofline.peak("TPU v5 lite", "hbm_bytes_per_s") == 819e9
    assert roofline.peak("TPU v5 lite", "bf16_flops_per_s") == 197e12
    with pytest.raises(KeyError):
        roofline.peak("cpu", "hbm_bytes_per_s")


def test_sweep_roofline_reader():
    cell = harness.resolve(ROOT, "graph500-s20.solve")
    reader = dict((m["name"], r) for m, r in cell.per_layer)["sweep_roofline"]
    n, m, sweeps = 1 << 20, 1 << 24, 3
    busy_ns = 2e9
    t = tr.Trace(device_ops=[[("fusion", 0, busy_ns)]],
                 spans=[("window", 0, 4e9), ("solve", 0, 3e9)])
    run = harness.Run(cell=cell, counters={"sweeps": sweeps, "n": n, "m": m},
                      trace=t, device_kind="TPU v5 lite")
    want = 100 * sweeps * roofline.sweep_bytes(n, m) / 819e9 / 2.0
    assert reader(run) == pytest.approx(want)
    # nothing to read: no trace, or a stream window
    assert reader(harness.Run(cell, {"sweeps": 3, "n": n, "m": m}, None,
                              "TPU v5 lite")) is None
    assert reader(harness.Run(cell, {"batches": 5}, t, "TPU v5 lite")) is None
