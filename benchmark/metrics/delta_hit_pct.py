"""Delta engine: share of the window's beats served without a full
re-upload and rescore, from the engine's own ``stats``."""


def read(record):
    stats = record["counters"]["engine"]
    beats = stats.get("beats", 0)
    if not beats:
        return None
    return 100.0 * (1 - stats["full_rescores"] / beats)
