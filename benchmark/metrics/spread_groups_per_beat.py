"""Delta engine: SPREAD groups placed per beat over the window (the
engine's ``stats["spread_groups"]`` over its ``beats``): the round-robin
fills a beat runs beside the water-fill.  None where the engine keeps no
such counter or took no beat."""


def read(record):
    stats = record["counters"]["engine"]
    beats = stats.get("beats", 0)
    if not beats or "spread_groups" not in stats:
        return None
    return stats["spread_groups"] / beats
