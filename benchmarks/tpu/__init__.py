"""Chip benchmark: one cell of ``BENCHMARK.json`` per run, on a TPU only.

See ``run.py`` for the command and ``harness.py`` for how a cell's files
(configuration, job, limits, metric readers) are found by name.
"""
