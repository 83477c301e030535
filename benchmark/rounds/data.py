"""The Ray Data deployment's rounds: SPREAD map tasks beside DEFAULT
tasks, placed by the raylet in one round.

Each of the traffic's ``pool_batches`` batches holds ``round_tasks``
TaskSpecs in an arrival order drawn from the seed: ``spread_share`` of
them SPREAD tasks of the ``spread_classes`` (one strategy object a
batch, as one ``.options()`` call builds it), the rest DEFAULT tasks of
the other classes, none with arguments.  A round hands the raylet one
batch (``_schedule_rows``).

SPREAD placement turns on the raylet's round-robin cursor, which the
module books in its own record as it books ``avail``: after every round,
warm-up rounds included, the cursor is one past the row of the round's
last placed SPREAD task (the last subgroup in first-appearance order,
its last task), read from the round's output.  So each round's starting
cursor is an input the reference takes from the module's record, never
from the program.  The program's cursor after the round (its
``SpreadSchedulingPolicy``'s) is read beside the output and compared
with the reference's as one more row of the placement counts.
"""

from __future__ import annotations

from typing import NamedTuple

import numpy as np

from ..reference import oracle
from ..reference import spread as ref
from . import hybrid


class Batch(NamedTuple):
    """One batch's tasks in arrival order, as the module drew them."""
    task_class: np.ndarray      # (T,)
    spread: np.ndarray          # (T,) bool: the SPREAD strategy
    tail: list                  # SPREAD tasks, the last placed first


def _tail(task_class, spread) -> list:
    """The SPREAD tasks in the reverse of their placement order: the
    subgroups last-appearing first, each one's tasks last first."""
    task_group, keys = ref.subgroups(task_class, spread)
    out = []
    for g in reversed(range(len(keys))):
        if keys[g][1]:
            out += np.flatnonzero(task_group == g)[::-1].tolist()
    return out


class Rounds(hybrid.Rounds):
    def __init__(self, dep, config: dict, traffic: dict):
        from ray_tpu.scheduling.policy import SchedulingType
        self.dep = dep
        self.entry = traffic["entry"]
        if self.entry != "rows":
            raise ValueError(f"the data rounds take entry 'rows', not "
                             f"{self.entry!r}")
        self.nodes = config["nodes"]
        self.pool = [self._batch(dep, dep.rng, traffic)
                     for _ in range(traffic["pool_batches"])]
        # the raylet's SPREAD policy, whose cursor is read after each round
        self._spread_policy = dep.raylet._policy._policies[
            SchedulingType.SPREAD]
        self.cursor = 0         # the module's record of the cursor
        # id(round output) -> (cursor before the round, program's after)
        self.cursors: dict[int, tuple[int, int]] = {}

    def _batch(self, dep, rng, traffic):
        """(records, Batch): ``round_tasks`` TaskSpecs, SPREAD ones over
        ``spread_classes`` and DEFAULT ones over the rest, class counts
        multinomial, arrival order shuffled."""
        from ray_tpu.common.ids import JobID, TaskID
        from ray_tpu.common.task_spec import (DEFAULT_STRATEGY,
                                              SchedulingStrategy,
                                              SchedulingStrategyKind,
                                              TaskSpec, TaskType)
        from ray_tpu.runtime.task_manager import TaskRecord

        size = traffic["round_tasks"]
        n_spread = int(round(size * traffic["spread_share"]))
        spread_classes = list(traffic["spread_classes"])
        other = [c for c in range(len(dep.class_cu))
                 if c not in spread_classes]
        classes = np.concatenate([
            np.repeat(spread_classes, rng.multinomial(
                n_spread, np.full(len(spread_classes),
                                  1 / len(spread_classes)))),
            np.repeat(other, rng.multinomial(
                size - n_spread, np.full(len(other), 1 / len(other))))])
        is_spread = np.arange(size) < n_spread
        order = rng.permutation(size)
        classes, is_spread = classes[order], is_spread[order]
        strategy = SchedulingStrategy(kind=SchedulingStrategyKind.SPREAD)
        job = JobID.from_int(1)
        recs = [TaskRecord(TaskSpec(
            task_id=TaskID.for_task(job), job_id=job,
            task_type=TaskType.NORMAL_TASK,
            function_descriptor="benchmark:data",
            resources=dep.request(int(c)),
            strategy=strategy if s else DEFAULT_STRATEGY), 0, [])
            for c, s in zip(classes.tolist(), is_spread.tolist())]
        return recs, Batch(classes, is_spread, _tail(classes, is_spread))

    def call(self, payload):
        recs, batch = payload
        start = self.cursor
        out = self.dep.raylet._schedule_rows(recs)
        after = self._spread_policy._cursor
        for t in batch.tail:
            if out[t] >= 0:
                self.cursor = (out[t] + 1) % self.nodes
                break
        self.cursors[id(out)] = (start, after if after < self.nodes else 0)
        return out


def _cursor_row(cursor: int, n: int) -> np.ndarray:
    """The cursor as one more row of the placement counts."""
    row = np.zeros((1, n + 1), np.int64)
    row[0, cursor] = 1
    return row


def reference_answer(record: dict, sample: dict, carry: bool = True):
    """(placed (G+1, N+1) — the last row marks the cursor after the
    round —, per-task rows, {class key: budgets}) from the per-task
    SPREAD + hybrid reference; ``carry=False`` is the control."""
    thr = record["config"]["system_config"]["scheduler_spread_threshold"]
    totals = record["totals"]
    rounds = record["rounds"]
    batch = rounds.pool[sample["index"]][1]
    start, _ = rounds.cursors[id(sample["out"])]
    placed, rows, after, cursor = ref.schedule(
        totals, sample["avail"], record["ref_vecs"], batch.task_class,
        batch.spread, thr, start, carry=carry)
    budgets = oracle.compute_budgets(totals, after, record["ref_vecs"],
                                     np.ones(len(totals), bool))
    return (np.concatenate([placed, _cursor_row(cursor, len(totals))]),
            rows, dict(zip(record["class_keys"], budgets)))


def program_answer(record: dict, sample: dict):
    """The program's per-task rows, counted by the reference's
    subgroups, and its cursor after the round, in the reference's
    form."""
    rounds = record["rounds"]
    batch = rounds.pool[sample["index"]][1]
    task_group, _keys = ref.subgroups(batch.task_class, batch.spread)
    rows = np.asarray(sample["out"])
    n = max(len(record["totals"]), int(rows.max()) + 1)
    placed = np.zeros((int(task_group.max()) + 1, n + 1), np.int64)
    np.add.at(placed, (task_group, np.where(rows < 0, n, rows)), 1)
    _, cursor = rounds.cursors[id(sample["out"])]
    return (np.concatenate([placed, _cursor_row(cursor, n)]), rows,
            sample["board"])
