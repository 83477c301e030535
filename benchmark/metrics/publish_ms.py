"""Raylet round: median per round of the time inside the program's span
``raylet.publish`` (re-keying the beat's lease budgets and publishing
them on the board), from the profiler trace."""

from benchmark.xtrace import span_ms


def read(record):
    return span_ms(record, "raylet.publish")
