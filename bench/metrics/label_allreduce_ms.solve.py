"""Collectives: device milliseconds per round of the sharded solve's
label exchange (ops under the program's ``label_allreduce`` scope: the
``pmin`` of the replicated labels across the chips), inside the solve
calls (the ``solve`` and ``labels_to_host`` spans), averaged over the
chips.  A collective waits for the slowest chip, so the wait counts."""
from bench import program_trace as pt

# the program's scope (repro.tracing.LABEL_ALLREDUCE)
LABEL_ALLREDUCE = "label_allreduce"


def read(run):
    return pt.scope_ms_per(run, [LABEL_ALLREDUCE],
                           ["solve", "labels_to_host"], "sweeps")
