"""Delta engine: median per round of the time inside the program's span
``delta.dispatch`` (the fused beat's dispatch), from the profiler trace."""

from benchmark.xtrace import span_ms


def read(record):
    return span_ms(record, "delta.dispatch")
