"""Raylet round: median per round of the time inside the program's span
``raylet.locality`` (the round's locality probe: each spec's avoid flag,
and the row holding most bytes of its plasma arguments), from the
profiler trace.  None where the program has no such span."""

from benchmark.xtrace import span_ms


def read(record):
    return span_ms(record, "raylet.locality")
