"""The shuffle deployment's rounds: the shuffling data loader's map and
reduce tasks, placed with pull locality.

Each of the traffic's ``pool_batches`` shuffle stages puts its
``mappers_per_stage`` map outputs on distinct rows of the configuration's
``data_nodes``, and splits each output into one chunk per reduce task,
sizes lognormal (``chunk_bytes``).  A round hands the raylet one stage's
``round_tasks`` TaskSpecs (``_schedule_rows``) in an arrival order drawn
from the seed: ``1 - reduce_share`` of them map tasks with no arguments,
the rest reduce tasks of ``reduce_classes``, reduce task ``r`` taking
chunk ``r`` of every map output as an ``ObjectRef`` argument.

Every chunk is registered as metadata only: its size on the head
raylet's store (``MemoryStore.put_remote``) and its one location, the
mapper's row, in the cluster's object directory.  So the raylet's
locality probe reads what a real shuffle's would, and no object byte
moves.  The module keeps its own record of each task's chunk rows and
sizes (``Stage``); the reference (``reference/locality.py``) computes the
preferred rows from that record, never from the program's directory.
"""

from __future__ import annotations

from typing import NamedTuple

import numpy as np

from ..reference import locality as ref
from ..reference import oracle
from . import hybrid


class Stage(NamedTuple):
    """One shuffle stage's tasks in arrival order, as the module drew
    them: each task's class, and its chunks' rows and bytes (-1 and 0
    for a map task)."""
    task_class: np.ndarray      # (T,)
    chunk_rows: np.ndarray      # (T, mappers)
    chunk_bytes: np.ndarray     # (T, mappers)


class Rounds(hybrid.Rounds):
    def __init__(self, dep, config: dict, traffic: dict):
        self.dep = dep
        self.entry = traffic["entry"]
        if self.entry != "rows":
            raise ValueError(f"the shuffle rounds take entry 'rows', not "
                             f"{self.entry!r}")
        rng = dep.rng
        self.data_nodes = np.sort(rng.choice(config["nodes"],
                                             config["data_nodes"],
                                             replace=False))
        self.pool = [self._stage(dep, rng, config, traffic, s)
                     for s in range(traffic["pool_batches"])]

    def _stage(self, dep, rng, config, traffic, stage: int):
        """(records, Stage) of one shuffle stage; registers its chunks."""
        from ray_tpu.common.ids import JobID, ObjectID, TaskID
        from ray_tpu.common.task_spec import TaskSpec, TaskType
        from ray_tpu.runtime.object_ref import ObjectRef
        from ray_tpu.runtime.task_manager import TaskRecord

        size = traffic["round_tasks"]
        n_reduce = int(round(size * traffic["reduce_share"]))
        reduce_classes = list(traffic["reduce_classes"])
        map_classes = [c for c in range(len(dep.class_cu))
                       if c not in reduce_classes]
        mappers = config["mappers_per_stage"]
        cb = config["chunk_bytes"]
        mapper_rows = rng.choice(self.data_nodes, mappers, replace=False)
        sizes = np.clip(rng.lognormal(np.log(cb["median"]), cb["sigma"],
                                      (n_reduce, mappers)),
                        cb["min"], cb["max"]).astype(np.int64)
        classes = np.concatenate([
            np.repeat(map_classes, rng.multinomial(
                size - n_reduce, np.full(len(map_classes),
                                         1 / len(map_classes)))),
            np.repeat(reduce_classes, rng.multinomial(
                n_reduce, np.full(len(reduce_classes),
                                  1 / len(reduce_classes))))])
        # tasks [0, size - n_reduce) map, then reduce task r at
        # size - n_reduce + r; the arrival order shuffles them
        order = rng.permutation(size)

        store, directory = dep.raylet.store, dep.cluster.directory
        refs = np.empty((n_reduce, mappers), object)
        for r in range(n_reduce):
            for j in range(mappers):
                # ids from the chunk's place, so a seed gives the same ones
                oid = ObjectID(((stage * n_reduce + r) * mappers + j + 1)
                               .to_bytes(ObjectID.SIZE, "big"))
                store.put_remote(oid, int(sizes[r, j]))
                directory.add_location(oid, int(mapper_rows[j]))
                refs[r, j] = ObjectRef(oid)

        job = JobID.from_int(1)
        recs = []
        chunk_rows = np.full((size, mappers), -1, np.int64)
        chunk_bytes = np.zeros((size, mappers), np.int64)
        for t, k in enumerate(order.tolist()):
            r = k - (size - n_reduce)
            args = tuple(refs[r]) if r >= 0 else ()
            if r >= 0:
                chunk_rows[t] = mapper_rows
                chunk_bytes[t] = sizes[r]
            recs.append(TaskRecord(TaskSpec(
                task_id=TaskID.for_task(job), job_id=job,
                task_type=TaskType.NORMAL_TASK,
                function_descriptor="benchmark:shuffle", args=args,
                resources=dep.request(int(classes[k]))), 0, []))
        return recs, Stage(classes[order], chunk_rows, chunk_bytes)


def _stage_of(record: dict, sample: dict):
    """(Stage, each task's preferred row) of a sampled round."""
    stage = record["rounds"].pool[sample["index"]][1]
    return stage, ref.preferred_rows(stage.chunk_rows, stage.chunk_bytes)


def reference_answer(record: dict, sample: dict, carry: bool = True):
    """(placed (G, N+1), per-task rows, {class key: budgets}) from the
    soft-affinity reference; ``carry=False`` is the control."""
    thr = record["config"]["system_config"]["scheduler_spread_threshold"]
    totals = record["totals"]
    stage, prefs = _stage_of(record, sample)
    placed, rows, after = ref.schedule(totals, sample["avail"],
                                       record["ref_vecs"], stage.task_class,
                                       prefs, thr, carry=carry)
    budgets = oracle.compute_budgets(totals, after, record["ref_vecs"],
                                     np.ones(len(totals), bool))
    return placed, rows, dict(zip(record["class_keys"], budgets))


def program_answer(record: dict, sample: dict):
    """The program's per-task rows, counted by the reference's
    subgroups into the reference's form."""
    stage, prefs = _stage_of(record, sample)
    task_group, _keys = ref.subgroups(stage.task_class, prefs)
    rows = np.asarray(sample["out"])
    n = max(len(record["totals"]), int(rows.max()) + 1)
    placed = np.zeros((int(task_group.max()) + 1, n + 1), np.int64)
    np.add.at(placed, (task_group, np.where(rows < 0, n, rows)), 1)
    return placed, rows, sample["board"]
