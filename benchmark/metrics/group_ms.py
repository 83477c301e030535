"""Raylet round: median per round of the time inside the program's span
``raylet.group`` (the subgrouping pass over the batch's TaskSpecs, then
packing the subgroups into arrays: two intervals a round), from the
profiler trace."""

from benchmark.xtrace import span_ms


def read(record):
    return span_ms(record, "raylet.group")
