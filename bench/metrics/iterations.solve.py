"""Contour fixpoint: sweeps per solve(), from ComponentResult.iterations,
averaged over the window's solves."""


def read(run):
    its = run.counters.get("iterations")
    return sum(its) / len(its) if its else None
