"""Device: share of the solve window in which no operation ran."""
from bench.metrics_common import idle_pct


def read(run):
    return idle_pct(run) if "sweeps" in run.counters else None
