"""Delta engine: real groups placed per beat over the window (the
engine's ``stats["groups"]`` over its ``beats``): the scan length a
localized round asks of the beat, one step per (class, preferred row,
avoid flag).  None where the engine keeps no such counter or took no
beat."""


def read(record):
    stats = record["counters"]["engine"]
    beats = stats.get("beats", 0)
    if not beats or "groups" not in stats:
        return None
    return stats["groups"] / beats
