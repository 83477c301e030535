"""The raylet round's one subgrouping pass (``Raylet._subgroup``, fed by
the round's locality probe ``Raylet._locality_rows``) and its
vectorised expansion (``raylet._expand``), pinned to the straightforward
per-task loops they replace.

Each case builds a batch of task records on a small live cluster (no
workers) and checks that the pass gives the reference loop's subgroups
in first-appearance order (task groups, counts, first tasks, locality
rows, avoid flags), consumes the avoid flags, counts the slow keys in
``queue_stats()``, that the expansion matches the per-task cursor loop,
and that a host-twin round and a device round of the batch give the
same rows.
"""

import numpy as np
import pytest

from ray_tpu.common.config import Config, get_config
from ray_tpu.common.ids import JobID, ObjectID, TaskID
from ray_tpu.common.resources import ResourceRequest
from ray_tpu.common.task_spec import (DEFAULT_STRATEGY, SchedulingStrategy,
                                      SchedulingStrategyKind, TaskSpec,
                                      TaskType)
from ray_tpu.runtime.object_ref import ObjectRef
from ray_tpu.runtime.raylet import _expand
from ray_tpu.runtime.serialization import serialize
from ray_tpu.runtime.task_manager import TaskRecord

CLASSES = [{"CPU": 1}, {"CPU": 2, "memory": 1}, {"CPU": 1, "memory": 4}]


@pytest.fixture
def cluster():
    from ray_tpu.cluster_utils import Cluster
    Config.reset({"health_check_period_ms": 3_600_000})
    c = Cluster()
    try:
        c.add_node(resources={"CPU": 8, "memory": 16}, num_workers=0)
        for _ in range(7):
            c.add_node(resources={"CPU": 4, "memory": 8}, num_workers=0)
        yield c
    finally:
        c.stop()


def _plasma_ref(cluster, row: int) -> ObjectRef:
    """An arena-sealed object whose one copy the directory puts on
    ``row``: a locality signal for the spec that takes it as an arg."""
    oid = ObjectID.from_random()
    cluster.store.put_serialized(oid, serialize(bytes(300_000)))
    cluster.directory.add_location(oid, row)
    return ObjectRef(oid)


def _spec(i, args=(), strategy=DEFAULT_STRATEGY):
    job = JobID.from_int(1)
    return TaskSpec(task_id=TaskID.for_task(job), job_id=job,
                    task_type=TaskType.NORMAL_TASK,
                    function_descriptor="subgroup:task", args=args,
                    resources=ResourceRequest(CLASSES[i % len(CLASSES)]),
                    strategy=strategy)


def _no_args(cluster, rng):
    return [_spec(int(c)) for c in rng.integers(0, 3, 64)], set()


def _locality_and_avoid(cluster, rng):
    refs = {row: _plasma_ref(cluster, row) for row in (2, 5)}
    specs, avoid = [], set()
    for t in range(64):
        kind = rng.integers(0, 5)
        if kind == 0:       # plasma arg: locality row 2 or 5
            args = (refs[(2, 5)[t % 2]],)
        elif kind == 1:     # an inline arg: probed, no locality row
            args = (t,)
        else:
            args = ()
        spec = _spec(int(rng.integers(0, 3)), args)
        if rng.random() < 0.2:      # lease-spilled: avoid wins
            avoid.add(spec.task_id)
        specs.append(spec)
    return specs, avoid


def _non_default(cluster, rng):
    specs, avoid = _locality_and_avoid(cluster, rng)
    specs[40] = _spec(1, strategy=SchedulingStrategy(
        kind=SchedulingStrategyKind.NODE_AFFINITY,
        node_id=cluster.crm.id_of(3), soft=True))
    return specs, avoid


def _spread(cluster, rng):
    # SPREAD specs, flagged ones and ones with args among them, form
    # their own subgroups: no locality row, no avoid flag
    specs, avoid = _locality_and_avoid(cluster, rng)
    spread = SchedulingStrategy(kind=SchedulingStrategyKind.SPREAD)
    for t in range(0, 64, 3):
        specs[t] = _spec(int(rng.integers(0, 3)), specs[t].args, spread)
        if t % 2:
            avoid.add(specs[t].task_id)
    return specs, avoid


def _distinct_equal_objects(cluster, rng):
    # equal classes through distinct request and strategy objects
    return [_spec(int(c), strategy=SchedulingStrategy() if t % 2
                  else DEFAULT_STRATEGY)
            for t, c in enumerate(rng.integers(0, 3, 64))], set()


def _host_twin_small(cluster, rng):
    ref = _plasma_ref(cluster, 3)
    specs = [_spec(0, (ref,)), _spec(0), _spec(1), _spec(0, (ref,)),
             _spec(1, (7,)), _spec(0)]
    return specs, {specs[2].task_id, specs[3].task_id}


def _reference_subgroups(raylet, specs, avoid):
    """The per-task loop the pass replaces: key (scheduling class,
    locality row or -1, avoid), first appearance decides the order; a
    SPREAD spec has neither a locality row nor an avoid flag."""
    groups, first, prefs, avoids, task_group = {}, [], [], [], []
    for t, s in enumerate(specs):
        spread = s.strategy.kind is SchedulingStrategyKind.SPREAD
        a = s.task_id in avoid and not spread
        pref = -1 if a or spread else raylet._locality_row(s)
        pref = -1 if pref is None else pref
        key = (s.scheduling_class(), pref, a)
        if key not in groups:
            groups[key] = len(first)
            first.append(t)
            prefs.append(pref)
            avoids.append(a)
        task_group.append(groups[key])
    return task_group, first, prefs, avoids


def _cursor_rows(task_group, counts):
    """The per-task cursor expansion ``_expand`` replaces."""
    n = counts.shape[1] - 1
    cols = np.concatenate([np.arange(n), [-1]])
    slots = [np.repeat(cols, counts[g]) for g in range(counts.shape[0])]
    cursor = [0] * counts.shape[0]
    rows = []
    for g in task_group:
        rows.append(int(slots[g][cursor[g]]))
        cursor[g] += 1
    return rows


def _set_avoid(raylet, avoid):
    with raylet._cv:
        raylet._avoid_local.clear()
        raylet._avoid_local.update(avoid)


@pytest.mark.parametrize("make", [
    _no_args, _locality_and_avoid, _non_default, _distinct_equal_objects,
    _host_twin_small, _spread], ids=lambda f: f.__name__.strip("_"))
def test_subgroup_pass_matches_per_task_loops(cluster, make):
    rng = np.random.default_rng(7)
    raylet = cluster.head()
    specs, avoid = make(cluster, rng)
    _set_avoid(raylet, avoid)
    before = raylet.queue_stats()
    sub = raylet._subgroup(specs, raylet._locality_rows(specs))
    after = raylet.queue_stats()
    kinds = {SchedulingStrategyKind.DEFAULT, SchedulingStrategyKind.SPREAD}
    if not all(s.strategy.kind in kinds for s in specs):
        # the mixed path: nothing consumed, nothing counted
        assert sub is None
        assert raylet._avoid_local == avoid
        assert after["group_tasks"] == before["group_tasks"]
        assert after["group_probed"] == before["group_probed"]
        rows = raylet._schedule_rows_soft(
            [TaskRecord(s, 0, []) for s in specs])
        assert len(rows) == len(specs)
        return

    task_group, first, prefs, avoids = _reference_subgroups(
        raylet, specs, avoid)
    assert sub.task_group.tolist() == task_group
    assert sub.first == first
    assert sub.prefs == prefs
    assert sub.avoids == avoids
    assert sub.spread == [specs[t].strategy.kind
                          is SchedulingStrategyKind.SPREAD for t in first]
    assert sub.counts.tolist() == np.bincount(
        task_group, minlength=len(first)).tolist()
    default = [s for s in specs
               if s.strategy.kind is SchedulingStrategyKind.DEFAULT]
    # the DEFAULT specs' flags consumed, the SPREAD specs' kept
    assert raylet._avoid_local == avoid - {s.task_id for s in default}
    slow = sum(1 for s in default if s.args or s.task_id in avoid)
    assert after["group_tasks"] - before["group_tasks"] == len(specs)
    assert after["group_probed"] - before["group_probed"] == slow
    if make in (_locality_and_avoid, _spread):
        assert {2, 5} <= set(prefs) and any(avoids)
    if make is _spread:
        assert any(sub.spread) and raylet._avoid_local

    # the expansion, on counts that place each subgroup anywhere
    n = len(raylet.crm.arrays()[0])
    counts = np.stack([rng.multinomial(c, np.full(n + 1, 1 / (n + 1)))
                       for c in sub.counts]).astype(np.int32)
    assert _expand(sub.task_group, counts) == _cursor_rows(
        sub.task_group, counts)

    # a host-twin round and a device round of the batch agree
    batch = [TaskRecord(s, 0, []) for s in specs]
    cfg = get_config()
    got = {}
    for name, floor in (("host", 10**9), ("device", 1)):
        _set_avoid(raylet, avoid)
        raylet._policy.spread.cursor = 0    # both from one SPREAD cursor
        cfg.scheduler_device_batch_min = floor
        got[name] = raylet._schedule_rows_soft(batch)
    assert got["host"] == got["device"]
    assert all(isinstance(r, int) for r in got["device"])
    assert any(r >= 0 for r in got["device"])
