"""Streaming engine: mean host milliseconds of the program's
``repro.ingest`` spans in the window, from the call into ``ingest()`` to
its return (validation, padding, plan, dispatch, edge store, commit;
the device work it dispatches runs on after it)."""
from bench import program_trace as pt


def read(run):
    if "batches" not in run.counters:
        return None
    trace = pt.for_run(run)
    spans = trace.span_ns(pt.INGEST_SPAN) if trace is not None else []
    return sum(spans) / len(spans) / 1e6 if spans else None
