"""The least bytes one placement beat with SPREAD groups must move.

Such a beat moves what any beat moves (``roofline.beat_bytes``), and
besides reads each group's strategy flag (1 byte) and the round-robin
cursor, and writes the cursor it leaves (4 bytes each):

    bytes = beat_bytes(N, R, G, C, d) + G + 8

with G the round's real groups, counted by the engine, not the
configuration's classes.
"""

from __future__ import annotations

from .roofline import beat_bytes


def spread_beat_bytes(nodes: int, columns: int, groups: float,
                      resident: int, dirty_rows: float) -> float:
    return (beat_bytes(nodes, columns, groups, resident, dirty_rows)
            + groups + 8)
