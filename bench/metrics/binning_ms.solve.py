"""MM sweep: device milliseconds per sweep of the radix binning around
the blocked kernel (ops under the program's ``binning`` scope: argsort,
permutation gathers, bin counts, padded scatters, chunk map) inside the
solve calls (the ``solve`` and ``labels_to_host`` spans)."""
from bench import program_trace as pt


def read(run):
    return pt.scope_ms_per(run, [pt.BINNING], ["solve", "labels_to_host"],
                           "sweeps")
