"""MM sweep: device milliseconds per sweep of XLA's scatter-min sweep
(ops under the program's ``scatter_min`` scope: the update stream's
gathers and the scatter-min into the labels) inside the solve calls (the
``solve`` and ``labels_to_host`` spans)."""
from bench import program_trace as pt


def read(run):
    return pt.scope_ms_per(run, [pt.SCATTER_MIN], ["solve", "labels_to_host"],
                           "sweeps")
