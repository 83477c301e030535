"""Kernels (``ops/hybrid_kernel.py``, ``ops/shard_reduce.py``): device
time per round of the programs the window runs, from the profiler trace,
averaged over the chips.  Per round, not per beat, so that it reads in a
cell whose rounds take no delta beat; where each round takes one beat,
as in every cell of the hybrid rounds, the two are the same."""


def read(record):
    summary = record.get("trace_summary") or {}
    rounds = len(record["round_s"])
    if not rounds or not summary.get("kernel_s"):
        return None
    return summary["kernel_s"] / rounds * 1e3
