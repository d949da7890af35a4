"""MM sweep under the frontier: device busy milliseconds inside the
``ingest`` spans, per batch."""


def read(run):
    b = run.counters.get("batches")
    if run.trace is None or not run.trace.device_ops or not b:
        return None
    return run.trace.busy_ns(["ingest"]) / 1e6 / b
