"""Delta engine: median per round of the time inside the program's span
``delta.view`` (the CRM's dirty-journal scan and the resync decision),
from the profiler trace."""

from benchmark.xtrace import span_ms


def read(record):
    return span_ms(record, "delta.view")
