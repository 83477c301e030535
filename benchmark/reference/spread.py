"""The benchmark's own CPU reference for a round with SPREAD tasks.

Ray's ``SpreadSchedulingPolicy``
(``src/ray/raylet/scheduling/policy/spread_scheduling_policy.cc``),
task by task, beside ``oracle.py``'s hybrid placement:

* subgroups: the round's tasks keyed (class, SPREAD or not) in order of
  first appearance, as the raylet groups them; each subgroup in order
  against the state the previous ones left;
* a SPREAD task visits the rows in rotation order from the round-robin
  cursor (``cursor, cursor+1, ..., n-1, 0, ...``) and takes the first
  on which its class is feasible and available (debiting its request),
  else the first on which it is feasible (queueing there, no debit),
  else none (-1); the cursor moves one past the row it took;
* a DEFAULT subgroup takes ``oracle.py``'s sequential hybrid placement,
  its tasks taking the placed rows ascending (``oracle.expand_rows``);
* budgets priced on the final state.

The cursor is carried from subgroup to subgroup and returned.  It
imports nothing from ``ray_tpu``.
"""

from __future__ import annotations

import numpy as np

from . import oracle


def subgroups(task_class, spread):
    """(task -> subgroup (T,), [(class, is SPREAD)] per subgroup), keyed
    (class, SPREAD or not) in order of first appearance."""
    index: dict[tuple[int, bool], int] = {}
    task_group = np.empty(len(task_class), np.int64)
    for t, key in enumerate(zip(np.asarray(task_class).tolist(),
                                np.asarray(spread, bool).tolist())):
        task_group[t] = index.setdefault(key, len(index))
    return task_group, list(index)


def spread_tasks(totals, avail, req, count: int, cursor: int):
    """``count`` SPREAD tasks of request ``req`` one by one from
    ``cursor``.  Returns (each task's row, avail after, cursor after)."""
    totals = np.asarray(totals, np.int64)
    state = np.asarray(avail, np.int64).copy()
    req = np.asarray(req, np.int64)
    n = totals.shape[0]
    pos = req > 0
    feasible = (totals[:, pos] >= req[pos]).all(axis=1)
    fits = feasible & (state[:, pos] >= req[pos]).all(axis=1)
    rows = []
    for _ in range(count):
        order = (np.arange(n) + cursor) % n
        node = -1
        for pool in (fits, feasible):
            cand = order[pool[order]]
            if cand.size:
                node = int(cand[0])
                break
        rows.append(node)
        if node < 0:
            continue
        if fits[node]:
            state[node] -= req
            fits[node] = bool((state[node, pos] >= req[pos]).all())
        cursor = (node + 1) % n
    return rows, state, cursor


def schedule(totals, avail, reqs, task_class, spread, spread_threshold: float,
             cursor: int, carry: bool = True):
    """Place one round from ``cursor``.  Returns ``(placed (G, N+1),
    rows (T,), avail_after (N, R), cursor after)``; ``carry=False`` is
    the control: every subgroup is placed against the round's starting
    availables and cursor."""
    totals = np.asarray(totals, np.int64)
    start = np.asarray(avail, np.int64)
    reqs = np.asarray(reqs, np.int64)
    n = totals.shape[0]
    mask = np.ones(n, bool)
    task_group, keys = subgroups(task_class, spread)
    counts = np.bincount(task_group, minlength=len(keys))
    placed = np.zeros((len(keys), n + 1), np.int32)
    group_rows = []
    state = start.copy()
    spent = np.zeros_like(state)
    cur = cursor
    for g, (c, is_spread) in enumerate(keys):
        if not carry:
            state, cur = start.copy(), cursor
        if is_spread:
            rows, after, cur = spread_tasks(totals, state, reqs[c],
                                            int(counts[g]), cur)
            np.add.at(placed[g], np.where(np.array(rows) < 0, n, rows), 1)
            group_rows.append(rows)
        else:
            row, after = oracle.schedule_grouped(
                totals, state, mask, reqs[c][None], [int(counts[g])],
                spread_threshold)
            placed[g] = row[0]
            group_rows.append(oracle.expand_rows(
                row, np.zeros(int(counts[g]), np.int64)).tolist())
        spent += state - after
        state = after
    taken = np.zeros(len(keys), np.int64)
    out = np.empty(len(task_class), np.int64)
    for t, g in enumerate(task_group):
        out[t] = group_rows[g][taken[g]]
        taken[g] += 1
    after = state if carry else start - spent
    return placed, out, after, cur
