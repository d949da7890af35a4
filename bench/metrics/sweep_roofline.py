"""MM sweep: share of the memory roofline.

The least time the window's sweeps could take, each moving
``bench.roofline.sweep_bytes(n, m)`` at the chip's HBM bandwidth, over
the device busy time inside the solve calls.  Integer compares are
negligible, so memory bounds it.
"""
from bench import roofline


def read(run):
    c = run.counters
    if run.trace is None or not run.trace.device_ops or not c.get("sweeps"):
        return None
    busy_s = run.trace.busy_ns(["solve", "labels_to_host"]) / 1e9
    if busy_s <= 0:
        return None
    least_s = (c["sweeps"] * roofline.sweep_bytes(c["n"], c["m"])
               / roofline.peak(run.device_kind, "hbm_bytes_per_s"))
    return 100.0 * least_s / busy_s
