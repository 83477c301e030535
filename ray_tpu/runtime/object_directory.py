"""Object directory: which simulated nodes hold a copy of each object.

Reference parity: upstream's ``ObjectDirectory`` (``src/ray/object_manager/
object_directory.cc``) tracks object locations (via GCS/owner subscription)
so the ``PullManager`` can pick transfer sources; per-node plasma stores
make locality real (SURVEY.md §1 layer 6, §3.3; mount empty).

Here the arena is physically one shared mapping (the simulated-cluster
form, like upstream's ``cluster_utils.Cluster`` on one machine), so
locality is a *directory* property: large (plasma-routed) objects are
born on the node that produced them and gain locations as pulls complete.
Small in-band values live in the owner's memory store and ship with task
specs — they have no directory entry, matching upstream (only plasma
objects transfer through the object manager).
"""

from __future__ import annotations

import threading
from itertools import chain, repeat
from typing import Iterable

from ..common.ids import ObjectID

# ids looked up per hold of a lock in a batched read, so a concurrent
# writer waits for one slice (well under a millisecond) at most
LOOKUP_SLICE = 2048


class ObjectDirectory:
    def __init__(self):
        self._lock = threading.Lock()
        # keyed by the id's raw bytes (``ObjectID.binary()``), which hash
        # and compare in C; public methods take and return ObjectIDs
        self._locs: dict[bytes, set[int]] = {}

    def add_location(self, object_id: ObjectID, row: int) -> None:
        key = object_id.binary()
        with self._lock:
            self._locs.setdefault(key, set()).add(row)

    def locations(self, object_id: ObjectID) -> tuple[int, ...]:
        key = object_id.binary()
        with self._lock:
            return tuple(sorted(self._locs.get(key, ())))

    def location_rows(self, keys: list[bytes]):
        """``locations`` of many ids in one pass, given as their raw keys
        (``ObjectID.binary()``), unsorted: (each id's number of
        locations, int64; their rows back to back in id order, int64).
        The lock is taken once per ``LOOKUP_SLICE`` ids."""
        import numpy as np
        counts: list[int] = []
        rows: list[int] = []
        for lo in range(0, len(keys), LOOKUP_SLICE):
            chunk = keys[lo:lo + LOOKUP_SLICE]
            with self._lock:
                got = list(map(self._locs.get, chunk,
                               repeat((), len(chunk))))
                counts.extend(map(len, got))
                rows.extend(chain.from_iterable(got))
        return (np.fromiter(counts, np.int64, len(counts)),
                np.fromiter(rows, np.int64, len(rows)))

    def has_location(self, object_id: ObjectID, row: int) -> bool:
        key = object_id.binary()
        with self._lock:
            return row in self._locs.get(key, ())

    def is_tracked(self, object_id: ObjectID) -> bool:
        key = object_id.binary()
        with self._lock:
            return key in self._locs

    def drop(self, object_ids: Iterable[ObjectID]) -> None:
        """Object deleted cluster-wide (refcount zero / loss)."""
        with self._lock:
            for oid in object_ids:
                self._locs.pop(oid.binary(), None)

    def sole_copies_on(self, row: int) -> list[ObjectID]:
        """Objects whose ONLY copy lives on ``row`` (a node holding any is
        not safe to terminate; the autoscaler migrates them first)."""
        with self._lock:
            return [ObjectID(key) for key, rows in self._locs.items()
                    if rows == {row}]

    def on_node_removed(self, row: int) -> list[ObjectID]:
        """Node death: its copies vanish.  Returns objects whose LAST copy
        was on the dead node — they are lost (upstream: reconstructed via
        lineage or surfaced as ObjectLostError, SURVEY §5.3)."""
        lost = []
        with self._lock:
            for key, rows in list(self._locs.items()):
                rows.discard(row)
                if not rows:
                    del self._locs[key]
                    lost.append(ObjectID(key))
        return lost

    def location_matrix(self, object_ids: list[ObjectID], n_rows: int):
        """(len(ids), n_rows) bool location mask for the pull kernel."""
        import numpy as np
        out = np.zeros((len(object_ids), n_rows), dtype=bool)
        with self._lock:
            for i, oid in enumerate(object_ids):
                for r in self._locs.get(oid.binary(), ()):
                    if r < n_rows:
                        out[i, r] = True
        return out

    def stats(self) -> dict:
        with self._lock:
            copies = sum(len(v) for v in self._locs.values())
            return {"num_tracked": len(self._locs), "num_copies": copies}
