"""MM sweep: device busy milliseconds per sweep inside the solve calls
(the ``solve`` and ``labels_to_host`` spans), whatever backend realises
the sweep."""


def read(run):
    sweeps = run.counters.get("sweeps")
    if run.trace is None or not run.trace.device_ops or not sweeps:
        return None
    return run.trace.busy_ns(["solve", "labels_to_host"]) / 1e6 / sweeps
