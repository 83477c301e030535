"""Delta engine: median per round of the time inside the program's span
``delta.upload`` (the full or delta sync: dirty-row staging, its
device_puts, the sync kernel's dispatch), from the profiler trace."""

from benchmark.xtrace import span_ms


def read(record):
    return span_ms(record, "delta.upload")
