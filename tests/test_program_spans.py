"""The placement round's profiler spans (``ray_tpu.util.tracing.phase``)
and the CRM's ``journal_scanned`` counter.

Each test runs beats or a raylet round under ``jax.profiler.trace`` on
the CPU and reads the trace back with ``benchmark.spans.load``: the
spans must cover their phases once per beat, nest inside ``delta.beat``,
and leave placements and budgets bit-exact.
"""

import os
import subprocess
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

from test_budget_beat import _oracle_budgets
from test_oracle import _churn_cluster, _mutate
from ray_tpu.scheduling import DeltaScheduler, schedule_grouped_oracle
from ray_tpu.util.tracing import PHASES

ROOT = Path(__file__).resolve().parent.parent
DELTA_LEAVES = [p for p in PHASES
                if p.startswith("delta.") and p != "delta.beat"]


def _engine(crm, shards):
    if shards <= 1:
        return DeltaScheduler(crm)
    from ray_tpu.scheduling.sharded_delta import ShardedDeltaScheduler
    return ShardedDeltaScheduler(crm, shards)


def _program_spans(log_dir) -> list:
    from benchmark import spans
    return sorted(([n, s, s + d] for n, s, d in spans.load(str(log_dir))
                   ["host"] if n in PHASES), key=lambda e: e[1])


def _churned_beats(tmp_path, shards, beats=3, seed=5):
    """``beats`` churned beats under a profiler session, after one
    untraced full-sync beat; returns the spans and each beat's answer
    with the oracle's."""
    rng, crm, ids, vecs, counts = _churn_cluster(seed=seed)
    eng = _engine(crm, shards)
    eng.beat(vecs, counts)
    debts, answers = [], []
    with jax.profiler.trace(str(tmp_path)):
        for _ in range(beats):
            _mutate(rng, crm, ids, debts)
            got = eng.beat(vecs, counts)
            budgets = np.stack([eng.budget_row_host(v) for v in vecs])
            answers.append((got, schedule_grouped_oracle(
                crm.snapshot(), vecs, counts), budgets,
                _oracle_budgets(crm, vecs, counts)))
    return _program_spans(tmp_path), answers


@pytest.mark.parametrize("shards", [1, 4])
def test_delta_leaves_once_per_beat_inside_beat(tmp_path, shards):
    events, _ = _churned_beats(tmp_path, shards)
    beats = [e for e in events if e[0] == "delta.beat"]
    assert len(beats) == 3
    for _, lo, hi in beats:
        inside = [n for n, s, e in events
                  if lo <= s and e <= hi and n != "delta.beat"]
        assert sorted(inside) == sorted(DELTA_LEAVES)
    # and no leaf outside a beat
    leaves = [e for e in events if e[0] in DELTA_LEAVES]
    assert len(leaves) == 3 * len(DELTA_LEAVES)


@pytest.mark.parametrize("shards", [1, 4])
def test_placements_and_budgets_exact_while_tracing(tmp_path, shards):
    _, answers = _churned_beats(tmp_path, shards, seed=17)
    for got, want, budgets, want_budgets in answers:
        np.testing.assert_array_equal(got, want)
        np.testing.assert_array_equal(budgets, want_budgets)


def test_raylet_round_emits_its_spans(tmp_path):
    from ray_tpu.cluster_utils import Cluster
    from ray_tpu.common.config import Config
    from ray_tpu.common.ids import JobID, TaskID
    from ray_tpu.common.resources import ResourceRequest
    from ray_tpu.common.task_spec import TaskSpec, TaskType
    from ray_tpu.runtime.task_manager import TaskRecord

    Config.reset({"scheduler_device_batch_min": 2,
                  "health_check_period_ms": 3_600_000})
    cluster = Cluster()
    try:
        cluster.add_node(resources={"CPU": 8, "memory": 16},
                         num_workers=0)
        for _ in range(6):
            cluster.add_node(resources={"CPU": 4, "memory": 8},
                             num_workers=0)
        raylet = cluster.head()
        job = JobID.from_int(1)

        def batch():
            return [TaskRecord(TaskSpec(
                task_id=TaskID.for_task(job), job_id=job,
                task_type=TaskType.NORMAL_TASK,
                function_descriptor="spans:task",
                resources=ResourceRequest({"CPU": 1 + i % 2})), 0, [])
                for i in range(12)]

        raylet._schedule_rows(batch())      # compiles outside the trace
        with jax.profiler.trace(str(tmp_path)):
            rows = raylet._schedule_rows(batch())
        assert all(r >= 0 for r in rows)
    finally:
        cluster.stop()
    names = [n for n, _, _ in _program_spans(tmp_path)]
    # the one subgrouping pass, then the device path's packing of its
    # subgroups: two intervals of one name
    assert names.count("raylet.group") == 2
    for name in ("raylet.overrides", "raylet.expand", "raylet.publish",
                 "delta.beat"):
        assert names.count(name) == 1, name


def test_locality_round_emits_the_probe_span(tmp_path):
    from ray_tpu.cluster_utils import Cluster
    from ray_tpu.common.config import Config
    from ray_tpu.common.ids import JobID, ObjectID, TaskID
    from ray_tpu.common.resources import ResourceRequest
    from ray_tpu.common.task_spec import TaskSpec, TaskType
    from ray_tpu.runtime.object_ref import ObjectRef
    from ray_tpu.runtime.task_manager import TaskRecord

    Config.reset({"scheduler_device_batch_min": 2,
                  "health_check_period_ms": 3_600_000})
    cluster = Cluster()
    try:
        for i in range(7):
            cluster.add_node(resources={"CPU": 4 + 4 * (i == 0),
                                        "memory": 8}, num_workers=0)
        raylet = cluster.head()
        refs = []
        for row in (1, 4):
            oid = ObjectID.from_random()
            raylet.store.put_remote(oid, 1 << 20)
            cluster.directory.add_location(oid, row)
            refs.append(ObjectRef(oid))
        job = JobID.from_int(1)

        def batch():
            return [TaskRecord(TaskSpec(
                task_id=TaskID.for_task(job), job_id=job,
                task_type=TaskType.NORMAL_TASK,
                function_descriptor="spans:task",
                args=(refs[i % 2],) if i % 3 else (),
                resources=ResourceRequest({"CPU": 1 + i % 2})), 0, [])
                for i in range(12)]

        raylet._schedule_rows(batch())      # compiles outside the trace
        with jax.profiler.trace(str(tmp_path)):
            rows = raylet._schedule_rows(batch())
        assert all(r >= 0 for r in rows)
        assert raylet._delta_engine.stats["pref_groups"] > 0
    finally:
        cluster.stop()
    events = _program_spans(tmp_path)
    names = [n for n, _, _ in events]
    # the probe splits the grouping pass: one more interval of
    # raylet.group, and the probe a leaf beside it
    assert names.count("raylet.locality") == 1
    assert names.count("raylet.group") == 3
    (_, lo, hi), = [e for e in events if e[0] == "raylet.locality"]
    assert not [n for n, s, e in events
                if n != "raylet.locality" and (lo <= s < hi or s <= lo < e)]
    for name in ("raylet.overrides", "raylet.expand", "raylet.publish",
                 "delta.beat"):
        assert names.count(name) == 1, name


def test_phase_names_follow_the_span_rule():
    from benchmark.xtrace import SPAN_NAME
    assert len(set(PHASES)) == len(PHASES)
    for name in PHASES:
        assert SPAN_NAME.match(name), name


def test_phase_imports_no_jax():
    code = ("import sys\n"
            "from ray_tpu.util.tracing import PHASES, phase\n"
            "spans = {id(phase(n)) for n in PHASES}\n"
            "for n in PHASES:\n"
            "    with phase(n):\n"
            "        pass\n"
            "assert len(spans) == 1, spans\n"
            "assert 'jax' not in sys.modules\n")
    env = {**os.environ, "PYTHONPATH": str(ROOT)}
    p = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=120)
    assert p.returncode == 0, p.stderr


def test_journal_scanned_counts_each_scan():
    from ray_tpu.common.resources import ResourceRequest
    rng, crm, ids, _vecs, _counts = _churn_cluster(seed=23)
    one = ResourceRequest({"CPU": 1})
    stats = crm.frozen_stats
    v = crm.delta_view(-2)[0]
    recycled = 0
    for step in range(12):
        for _ in range(1 + step % 4):
            crm.force_subtract(int(rng.integers(0, len(ids))), one)
        log = len(crm._dirty_log)
        scanned, patched = stats["journal_scanned"], stats["patched"]
        since = -2 if step % 3 == 2 else v      # -2: resync, no view scan
        v, *_rest, rows = crm.delta_view(since)
        del _rest
        scans = stats["patched"] - patched + (rows is not None)
        recycled += stats["patched"] - patched
        assert stats["journal_scanned"] - scanned == log * scans
        # a view of the current epoch reads nothing
        scanned = stats["journal_scanned"]
        crm.delta_view(v)
        assert stats["journal_scanned"] == scanned
    assert recycled > 0
