"""Streaming engine: device milliseconds per batch of the passes over the
labels and the frontier (ops under the program's ``compact``,
``compress`` or ``pointer_jump`` scope) inside the ``ingest`` spans."""
from bench import program_trace as pt


def read(run):
    return pt.scope_ms_per(run, [pt.COMPACT, pt.COMPRESS, pt.POINTER_JUMP],
                           ["ingest"], "batches")
