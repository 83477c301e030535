"""Delta engine: median per round of the time inside the program's span
``delta.readback`` (the beat's one readback, which waits for the
device), from the profiler trace."""

from benchmark.xtrace import span_ms


def read(record):
    return span_ms(record, "delta.readback")
