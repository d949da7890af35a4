"""MM sweep on a mesh: one chip's share of the memory roofline.

Each chip sweeps only its own block of the edges, ``m / chips`` of them,
against its whole replica of the labels.  So the least time of the
window's sweeps on a chip is ``sweeps x bench.roofline.sweep_bytes(n,
m / chips)`` at the chip's HBM bandwidth; this is that over the device
busy time inside the solve calls, averaged over the chips.
"""
from bench import roofline


def read(run):
    c = run.counters
    if run.trace is None or not run.trace.device_ops or not c.get("sweeps"):
        return None
    busy_s = run.trace.busy_ns(["solve", "labels_to_host"]) / 1e9
    if busy_s <= 0:
        return None
    shard_m = c["m"] // run.cell.chips
    least_s = (c["sweeps"] * roofline.sweep_bytes(c["n"], shard_m)
               / roofline.peak(run.device_kind, "hbm_bytes_per_s"))
    return 100.0 * least_s / busy_s
