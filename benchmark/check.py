"""``correct``: the sampled rounds of the window against the reference.

For every round the sampler kept, the configuration's round module
(``rounds/<name>.py``) gives the reference's answer, from the
benchmark's own reference (``reference/``) on the generator's own record
of the cluster at that round (drawn totals and usage, booked by every
churn op; every node alive, none suspect) and the module's own record of
what it adds, and the program's answer in the same form.  Three numbers
count where they differ:

* ``placement_cells_off``: (class, node) cells, the column of tasks no
  node holds included, whose task count differs;
* ``task_rows_off``: tasks whose node row differs (rounds of task
  records; the rows follow the raylet's expansion);
* ``budget_cells_off``: (class, node) lease budgets on the budget board
  that differ, a missing class counting every node (rounds that
  published).

Nothing the reference reads comes from the program: not the CRM's
availables, masks or class vectors.  Where the program's arrays hold
spare rows beyond the cluster's nodes, the reference's answer for them
is 0.  The configuration states bit-exact placement, so each limit is 0.
``judge(record, produce=control)`` puts the control in the program's
place: the reference with the sequential class order broken.
"""

from __future__ import annotations

import numpy as np

LIMITS = {"placement_cells_off": 0, "task_rows_off": 0,
          "budget_cells_off": 0}


def _pad_rows(want: np.ndarray, width: int, last_is_none: bool):
    """``want`` with 0 for the program's spare rows, up to ``width``
    columns (the column of tasks no node holds stays last)."""
    spare = width - want.shape[-1]
    if spare <= 0:
        return want
    zeros = np.zeros(want.shape[:-1] + (spare,), want.dtype)
    if last_is_none:
        return np.concatenate([want[..., :-1], zeros, want[..., -1:]], -1)
    return np.concatenate([want, zeros], -1)


def control(record: dict, sample: dict):
    """The reference in the program's place, with the classes placed
    against the round's starting state instead of one after another."""
    return record["module"].reference_answer(record, sample, carry=False)


def judge(record: dict, produce=None) -> dict:
    """{name: {"value": n, "limit": 0}} over the sampled rounds; the
    program's answers unless ``produce`` stands in for them."""
    module = record["module"]
    produce = produce or module.program_answer
    off = {k: 0 for k in LIMITS}
    has_rows = has_board = False
    for sample in record["samples"]:
        want_p, want_r, want_b = module.reference_answer(record, sample)
        got_p, got_r, got_b = produce(record, sample)
        want_p = _pad_rows(want_p, got_p.shape[-1], True)
        if got_p.shape != want_p.shape:
            off["placement_cells_off"] += want_p.size
        else:
            off["placement_cells_off"] += int((got_p != want_p).sum())
        if want_r is not None:
            has_rows = True
            got_r = np.asarray(got_r)
            off["task_rows_off"] += (int((got_r != want_r).sum())
                                     if got_r.shape == want_r.shape
                                     else want_r.size)
        if got_b is not None:
            has_board = True
            for key, want in want_b.items():
                got = got_b.get(key)
                if got is not None:
                    want = _pad_rows(want, np.shape(got)[-1], False)
                if got is None or np.shape(got) != want.shape:
                    off["budget_cells_off"] += want.size
                else:
                    off["budget_cells_off"] += int(
                        (np.asarray(got) != want).sum())
    if not has_rows:
        off.pop("task_rows_off")
    if not has_board:
        off.pop("budget_cells_off")
    return {k: {"value": v, "limit": LIMITS[k]} for k, v in off.items()}


def passed(checks: dict, record: dict) -> bool:
    return bool(record["samples"]) and all(
        c["value"] <= c["limit"] for c in checks.values())
