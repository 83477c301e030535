"""Device (several chips): per beat, the time of collective operations
(all-reduce, all-gather, ...) during which no other operation runs on
that chip, from the trace, averaged over the chips."""


def read(record):
    summary = record.get("trace_summary") or {}
    beats = record["counters"]["engine"].get("beats", 0)
    if record["cell"]["chips"] < 2 or not beats \
            or not summary.get("collective_ops"):
        return None
    return summary["collective_exposed_s"] / beats * 1e3
