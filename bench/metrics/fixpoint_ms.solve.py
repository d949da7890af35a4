"""Contour fixpoint: device milliseconds per sweep of the work around the
sweeps, label compression and the early-convergence test (ops under the
program's ``pointer_jump`` or ``converge_check`` scope), inside the solve
calls (the ``solve`` and ``labels_to_host`` spans)."""
from bench import program_trace as pt


def read(run):
    return pt.scope_ms_per(run, [pt.POINTER_JUMP, pt.CONVERGE_CHECK],
                           ["solve", "labels_to_host"], "sweeps")
