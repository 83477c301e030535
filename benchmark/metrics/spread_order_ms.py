"""Raylet round: median per round of the time inside the program's span
``raylet.spread_order`` (each SPREAD subgroup's rows put in the order the
round-robin hands them out), from the profiler trace.  None where the
program has no such span."""

from benchmark.xtrace import span_ms


def read(record):
    return span_ms(record, "raylet.spread_order")
