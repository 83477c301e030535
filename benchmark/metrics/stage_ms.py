"""Delta engine: median per round of the time inside the program's span
``delta.stage`` (request normalisation, class interning, padding,
override packing), from the profiler trace."""

from benchmark.xtrace import span_ms


def read(record):
    return span_ms(record, "delta.stage")
