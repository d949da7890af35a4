"""Chip benchmark of the connectivity system: one cell per run.

See ``bench/run.py`` for the command, ``BENCHMARK.json`` for the cells
and metrics, and ``PERF.md`` for why each exists.
"""
