"""Device: share of the chip's idle time inside the window's rounds that
the program's leaf spans cover, averaged over the chips, from the
profiler trace: how much of the wait the spans name.

It checks the trace's coverage, not the cost of a layer, and should move
no end-to-end metric: a change that removes span-covered host wait can
lower it while ``placed_per_s`` rises.  It falls where the chip waits on
host work that no span names, which is where a span is missing."""


def read(record):
    summary = record.get("trace_summary") or {}
    idle = summary.get("idle_in_rounds_s")
    if not idle:
        return None
    return 100.0 * summary["idle_explained_s"] / idle
