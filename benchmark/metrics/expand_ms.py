"""Raylet round: median per round of the time inside the program's span
``raylet.expand`` (placed counts to one node row per task), from the
profiler trace."""

from benchmark.xtrace import span_ms


def read(record):
    return span_ms(record, "raylet.expand")
