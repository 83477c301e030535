"""Kernels: a SPREAD beat's share of its roofline.  The least bytes of a
beat with SPREAD groups (``benchmark/spread_roofline.py``, its groups
counted by the engine) over the chips' HBM bandwidth
(``benchmark/peaks.py``), divided by the measured device time per beat.
None where the engine counts no SPREAD groups or no beat ran."""

from benchmark.peaks import peaks
from benchmark.spread_roofline import spread_beat_bytes


def read(record):
    summary = record.get("trace_summary") or {}
    stats = record["counters"]["engine"]
    beats = stats.get("beats", 0)
    if not beats or not stats.get("spread_groups") \
            or not summary.get("kernel_s"):
        return None
    config = record["config"]
    chips = record["cell"]["chips"]
    least = spread_beat_bytes(config["nodes"], len(config["node_columns"]),
                              stats["groups"] / beats, config["classes"],
                              stats.get("rows_uploaded", 0) / beats)
    bound_s = least / (peaks(record["device"]["kind"])["hbm_bytes_per_s"]
                       * chips)
    return 100.0 * bound_s / (summary["kernel_s"] / beats)
