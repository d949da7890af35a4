"""Arithmetic that several per-layer metric readers share."""


def idle_pct(run):
    """100 x (1 - device busy time / window), from the trace; None
    without a trace or a device plane in it."""
    if run.trace is None or not run.trace.device_ops:
        return None
    lo, hi = run.trace.window()
    return 100.0 * (1.0 - run.trace.busy_ns() / (hi - lo))
