"""Streaming engine: delta-solve sweeps per batch, from the change of the
snapshot's cumulative ``iterations`` over the window's batches."""


def read(run):
    return run.counters.get("iterations_per_batch")
