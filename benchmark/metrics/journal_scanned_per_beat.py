"""Delta engine: entries of the CRM's dirty journal scanned per beat over
the window (the CRM's ``frozen_stats["journal_scanned"]`` over the
engine's ``beats``)."""


def read(record):
    counts = record["counters"]
    beats = counts["engine"].get("beats", 0)
    scanned = counts["crm"].get("journal_scanned")
    if not beats or scanned is None:
        return None
    return scanned / beats
