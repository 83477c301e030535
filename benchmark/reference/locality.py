"""The benchmark's own CPU reference for a round with pull locality.

Ray's locality-aware leasing (``src/ray/core_worker/lease_policy.cc``,
``LocalityAwareLeasePolicy``) sends a task to the node that holds the
most bytes of its arguments; the raylet then places it there by soft
node affinity and falls back to the hybrid policy.  Here, from the round
module's own record of each task's argument locations and sizes (never
from the program's object directory):

* preferred row: the row holding the most bytes of the task's chunks,
  the lowest row on ties; none for a task with no chunks;
* subgroups: the round's tasks keyed (class, preferred row) in order of
  first appearance, as the raylet groups them;
* each subgroup in order: a preferred row on which the class is
  feasible takes every task of the subgroup, debiting
  ``min(count, cap)`` requests of its availables (``cap`` = how many
  fit), the rest queueing there; otherwise the subgroup takes
  ``oracle.py``'s sequential hybrid placement;
* budgets priced on the final state, rows expanded by
  ``oracle.expand_rows``.

It imports nothing from ``ray_tpu``.
"""

from __future__ import annotations

import numpy as np

from . import oracle


def preferred_rows(chunk_rows, chunk_bytes) -> np.ndarray:
    """(T,) the row holding the most bytes of each task's chunks, lowest
    row on ties; -1 where a task has none (``chunk_rows`` -1 pads)."""
    out = np.full(len(chunk_rows), -1, np.int64)
    for t, (rows, sizes) in enumerate(zip(chunk_rows, chunk_bytes)):
        by_row: dict[int, int] = {}
        for r, s in zip(rows.tolist(), sizes.tolist()):
            if r >= 0:
                by_row[r] = by_row.get(r, 0) + s
        if by_row:
            out[t] = min(by_row, key=lambda r: (-by_row[r], r))
    return out


def subgroups(task_class, prefs):
    """(task -> subgroup (T,), [(class, preferred row)] per subgroup),
    keyed (class, preferred row) in order of first appearance."""
    index: dict[tuple[int, int], int] = {}
    task_group = np.empty(len(task_class), np.int64)
    for t, key in enumerate(zip(np.asarray(task_class).tolist(),
                                np.asarray(prefs).tolist())):
        task_group[t] = index.setdefault(key, len(index))
    return task_group, list(index)


def schedule(totals, avail, reqs, task_class, prefs,
             spread_threshold: float, carry: bool = True):
    """Place one round.  Returns ``(placed (G, N+1), rows (T,),
    avail_after (N, R))``; ``carry=False`` is the control: every
    subgroup is placed against the round's starting availables."""
    totals = np.asarray(totals, np.int64)
    start = np.asarray(avail, np.int64)
    reqs = np.asarray(reqs, np.int64)
    n = totals.shape[0]
    mask = np.ones(n, bool)
    task_group, keys = subgroups(task_class, prefs)
    counts = np.bincount(task_group, minlength=len(keys))
    placed = np.zeros((len(keys), n + 1), np.int32)
    state = start.copy()
    spent = np.zeros_like(state)
    for g, (c, p) in enumerate(keys):
        if not carry:
            state = start.copy()
        req, count = reqs[c], int(counts[g])
        pos = req > 0
        if p >= 0 and (totals[p][pos] >= req[pos]).all():
            cap = int((state[p][pos] // req[pos]).min()) if pos.any() \
                else count
            debit = req * min(count, max(cap, 0))
            state[p] -= debit
            spent[p] += debit
            placed[g, p] = count
            continue
        row, after = oracle.schedule_grouped(
            totals, state, mask, req[None], [count], spread_threshold)
        placed[g] = row[0]
        spent += state - after
        state = after
    after = state if carry else start - spent
    return placed, oracle.expand_rows(placed, task_group), after
