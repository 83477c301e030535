"""The least bytes one localized placement beat must move.

A beat whose groups carry preferred rows and avoid flags
(``fused_beat``'s localized program) moves what any beat moves
(``roofline.beat_bytes``), and besides reads each group's preferred row
(4 bytes) and avoid flag (1 byte), and the preferred row's totals and
availables (``8R``):

    bytes = beat_bytes(N, R, G, C, d) + G(5 + 8R)

with G the round's real groups, counted by the engine, not the
configuration's classes.
"""

from __future__ import annotations

from .roofline import beat_bytes


def localized_beat_bytes(nodes: int, columns: int, groups: float,
                         resident: int, dirty_rows: float) -> float:
    return (beat_bytes(nodes, columns, groups, resident, dirty_rows)
            + groups * (5 + 8 * columns))
