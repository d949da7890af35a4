"""MM sweep: device milliseconds per sweep of the Mosaic scatter-min
kernel (ops under the program's ``scatter_kernel`` scope) inside the
solve calls (the ``solve`` and ``labels_to_host`` spans)."""
from bench import program_trace as pt


def read(run):
    return pt.scope_ms_per(run, [pt.SCATTER_KERNEL],
                           ["solve", "labels_to_host"], "sweeps")
