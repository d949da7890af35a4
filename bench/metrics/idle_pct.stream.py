"""Device: share of the stream window in which no operation ran."""
from bench.metrics_common import idle_pct


def read(run):
    return idle_pct(run) if "batches" in run.counters else None
