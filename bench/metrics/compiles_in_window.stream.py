"""JIT and compile: executables the runtime built or loaded inside the
stream's window (edge-store growth, new pad buckets), counted by a
``jax.monitoring`` listener."""


def read(run):
    if "batches" not in run.counters:
        return None
    return run.counters.get("compiles_in_window")
