"""Kernels: the beat's share of its roofline.  The least bytes a beat
must move (``benchmark/roofline.py``, from the cell's shapes) over the
chips' HBM bandwidth (``benchmark/peaks.py``), divided by the measured
device time per beat.  The beat is int32 work with no published peak, so
the bandwidth bound alone is used."""

from benchmark.peaks import peaks
from benchmark.roofline import beat_bytes


def read(record):
    summary = record.get("trace_summary") or {}
    stats = record["counters"]["engine"]
    beats = stats.get("beats", 0)
    if not beats or not summary.get("kernel_s"):
        return None
    config = record["config"]
    # a round that reaches the device holds every class of the cell
    groups = config["classes"]
    chips = record["cell"]["chips"]
    least = beat_bytes(config["nodes"], len(config["node_columns"]), groups,
                       config["classes"],
                       stats.get("rows_uploaded", 0) / beats)
    bound_s = least / (peaks(record["device"]["kind"])["hbm_bytes_per_s"]
                       * chips)
    return 100.0 * bound_s / (summary["kernel_s"] / beats)
