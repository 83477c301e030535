"""The hybrid deployment's rounds: default-strategy tasks of the
configuration's classes, placed by the hybrid pack/spread policy.

A configuration whose file has no ``rounds`` key runs these.  The
traffic mix's ``entry`` picks the raylet's entry:

* ``delta`` hands the raylet the whole backlog as class counts
  (``_schedule_rows_delta``) every round;
* ``rows`` hands it a batch of task records (``_schedule_rows``) from a
  seeded pool of ``pool_batches`` batches of ``round_tasks``.

The reference (``reference/oracle.py``) places the round's classes in
the order the raylet groups them, by class alone: the tasks carry no
arguments and no avoid flag, so no locality row or soft affinity enters.
"""

from __future__ import annotations

import numpy as np

from ..reference import oracle as ref


class Rounds:
    """The rounds a traffic mix offers, in order, for ever.  Each round
    yields ``(index, payload)``: the batch's place in the pool, and what
    the raylet is handed."""

    def __init__(self, dep, config: dict, traffic: dict):
        self.dep = dep
        self.entry = traffic["entry"]
        rng = dep.rng
        g = len(dep.class_cu)
        if self.entry == "delta":
            self.counts = rng.multinomial(
                config["pending_tasks"], np.full(g, 1 / g)).astype(np.int32)
            self.pool = []
            return
        if self.entry != "rows":
            raise ValueError(f"unknown entry {self.entry!r}")
        self.pool = [self._batch(dep, rng, traffic["round_tasks"])
                     for _ in range(traffic["pool_batches"])]

    @staticmethod
    def _batch(dep, rng, size: int):
        """(records, task_class): ``size`` default-strategy TaskSpecs with
        no arguments, class counts multinomial and arrival order shuffled."""
        from ray_tpu.common.ids import JobID, TaskID
        from ray_tpu.common.task_spec import TaskSpec, TaskType
        from ray_tpu.runtime.task_manager import TaskRecord

        g = len(dep.class_cu)
        counts = rng.multinomial(size, np.full(g, 1 / g))
        task_class = rng.permutation(np.repeat(np.arange(g), counts))
        job = JobID.from_int(1)
        recs = [TaskRecord(TaskSpec(
            task_id=TaskID.for_task(job), job_id=job,
            task_type=TaskType.NORMAL_TASK,
            function_descriptor="benchmark:task",
            resources=dep.request(int(c))), 0, [])
            for c in task_class]
        return recs, task_class

    def at(self, i: int):
        if self.entry == "delta":
            return 0, self.counts
        return i % len(self.pool), self.pool[i % len(self.pool)]

    def call(self, payload):
        """The raylet's placement entry on one round's payload."""
        raylet = self.dep.raylet
        if self.entry == "delta":
            return raylet._schedule_rows_delta(self.dep.class_vecs, payload)
        return raylet._schedule_rows(payload[0])

    def tally(self, payload, out) -> tuple[int, int]:
        """(tasks offered, tasks given a node row) in one round."""
        if self.entry == "delta":
            return int(payload.sum()), int(out[:, :-1].sum())
        return len(out), len(out) - out.count(-1)


def _round_inputs(record: dict, sample: dict):
    """(class vectors in group order, counts, task -> group or None)."""
    vecs, rounds = record["ref_vecs"], record["rounds"]
    if rounds.entry == "delta":
        return vecs, rounds.counts, None
    _recs, task_class = rounds.pool[sample["index"]]
    order = list(dict.fromkeys(int(c) for c in task_class))  # first seen
    group_of = {c: g for g, c in enumerate(order)}
    task_group = np.array([group_of[int(c)] for c in task_class])
    counts = np.bincount(task_group, minlength=len(order))
    return vecs[order], counts, task_group


def reference_answer(record: dict, sample: dict, carry: bool = True):
    """(placed (G, N+1), per-task rows or None, {class key: budgets});
    with ``carry=False`` each class is placed against the round's
    starting state (the control)."""
    thr = record["config"]["system_config"]["scheduler_spread_threshold"]
    totals = record["totals"]
    mask = np.ones(len(totals), bool)
    vecs, counts, task_group = _round_inputs(record, sample)
    placed, after = ref.schedule_grouped(totals, sample["avail"], mask,
                                         vecs, counts, thr, carry=carry)
    rows = None if task_group is None else ref.expand_rows(placed,
                                                           task_group)
    budgets = ref.compute_budgets(totals, after, record["ref_vecs"], mask)
    return placed, rows, dict(zip(record["class_keys"], budgets))


def program_answer(record: dict, sample: dict):
    """The program's answer to a sampled round, in the reference's form."""
    out = sample["out"]
    if record["rounds"].entry == "delta":
        placed, rows = np.asarray(out), None
    else:
        _v, _c, task_group = _round_inputs(record, sample)
        rows = np.asarray(out)
        n = max(len(record["totals"]), int(rows.max()) + 1)
        placed = np.zeros((int(task_group.max()) + 1, n + 1), np.int64)
        np.add.at(placed, (task_group, np.where(rows < 0, n, rows)), 1)
    return placed, rows, sample["board"]
