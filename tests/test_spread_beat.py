"""SPREAD subgroups on the resident delta beat.

``fused_beat``'s SPREAD variant (``DeltaScheduler.beat(spread=...)``)
and the host twin (``spread_group_host`` beside ``schedule_group_host``)
against the per-task ``SpreadSchedulingPolicy`` + ``HybridSchedulingPolicy``
sequence the raylet's per-task path runs: counts, each SPREAD task's row
(pass by pass in rotation order from the cursor, ``_spread_order``), the
end cursor and the resulting ``avail``, under CRM churn with the cursor
carried from beat to beat.  Then raylet rounds: the device path, the
host twin and the per-task path agree, a SPREAD spec takes no probe row
and keeps its avoid flag, a sharded engine sends SPREAD rounds to the
host twin, and strategy mixes in one bucket share one program.  Seeded
throughout.
"""

import numpy as np
import pytest

from test_oracle import _churn_cluster, _mutate
from ray_tpu.ops import hybrid_kernel as hk
from ray_tpu.ops.hybrid_kernel import schedule_group_host, spread_group_host
from ray_tpu.runtime.raylet import _spread_order
from ray_tpu.scheduling import DeltaScheduler
from ray_tpu.scheduling.contract import compute_budgets, threshold_fp
from ray_tpu.scheduling.oracle import ClusterState
from ray_tpu.scheduling.policy import (CompositeSchedulingPolicy,
                                       SchedulingOptions, SchedulingType)

THR = threshold_fp(None)


def _per_task(totals, avail, mask, reqs, counts, spread, cursor):
    """The per-task path: each group's tasks one at a time through the
    composite policy, SPREAD or hybrid.  Returns (counts (G, N+1), each
    SPREAD group's rows in turn, end cursor, avail after)."""
    pol = CompositeSchedulingPolicy()
    pol.spread.cursor = cursor
    state = ClusterState(totals, np.asarray(avail, np.int32).copy(), mask)
    n = state.num_nodes
    placed = np.zeros((len(reqs), n + 1), np.int64)
    orders = {}
    for g, (req, c, sp) in enumerate(zip(reqs, counts, spread)):
        opts = SchedulingOptions(scheduling_type=SchedulingType.SPREAD
                                 if sp else SchedulingType.HYBRID)
        rows = [pol.schedule(state, req, opts) for _ in range(int(c))]
        for r in rows:
            placed[g, r if r >= 0 else n] += 1
        if sp:
            orders[g] = rows
    return placed, orders, pol.spread.cursor, state.avail


def _host_twin(totals, avail, mask, reqs, counts, spread, cursor):
    """The raylet's host twin, group by group."""
    n = totals.shape[0]
    avail = np.asarray(avail, np.int64).copy()
    placed, orders = [], {}
    for g, (req, c, sp) in enumerate(zip(reqs, counts, spread)):
        if sp:
            row, queued, avail, end = spread_group_host(
                avail, totals, mask, req, c, cursor)
            orders[g] = _spread_order(row, queued, cursor, n).tolist()
            cursor = end
        else:
            row, avail = schedule_group_host(avail, totals, mask, req, c,
                                             None, THR)
        placed.append(row)
    return np.stack(placed), orders, cursor, avail


def _device_orders(eng, got, spread, cursor):
    """Each SPREAD group's rows in turn from a beat's readback."""
    queued, after = eng.last_spread()
    n = got.shape[1] - 1
    return {g: _spread_order(got[g], queued[g],
                             int(after[g - 1]) if g else cursor, n).tolist()
            for g in np.flatnonzero(spread)}, int(after[-1])


CASES = {
    # (seed, nodes, CRM rows, task scale, first cursor, extra class)
    "mixed_orders": (1, 24, 32, 12, 0, None),
    "exhausts_into_feasible": (2, 24, 32, 160, 5, None),
    "masked_rows": (3, 24, 32, 20, 7, None),
    "cursor_wraps": (4, 24, 32, 30, 30, None),
    "zero_request": (5, 24, 32, 20, 3, {}),
    "rows_not_pow2_under_pad": (6, 27, 27, 40, 26, None),
    "infeasible_class": (7, 24, 32, 20, 11, {"CPU": 64}),
}


@pytest.mark.parametrize("case", list(CASES))
def test_spread_beat_matches_per_task_and_host_twin(case):
    from ray_tpu.common.resources import ResourceRequest
    seed, n_nodes, capacity, scale, cursor, extra_class = CASES[case]
    rng, crm, ids, vecs, _ = _churn_cluster(seed=seed, n_nodes=n_nodes,
                                            capacity=capacity)
    if extra_class is not None:
        vecs = np.concatenate([vecs, crm.intern_request(
            ResourceRequest(extra_class))[None, :vecs.shape[1]]])
    eng = DeltaScheduler(crm)
    debts = []
    k = vecs.shape[0]
    # groups in a mixed first-appearance order, each class once SPREAD
    # and once DEFAULT
    order = rng.permutation(2 * k)
    g_vecs = np.concatenate([vecs, vecs])[order]
    spread = (order < k).tolist()
    soft = None
    if case == "masked_rows":
        for i in (2, 9, 17):
            crm.set_draining(ids[i], True)
        soft = np.ones(capacity, bool)
        soft[[4, 12]] = False
    stats0 = dict(eng.stats)
    for beat in range(3):
        _mutate(rng, crm, ids, debts)
        g_counts = rng.integers(1, scale + 1, 2 * k).astype(np.int32)
        got = eng.beat(g_vecs, g_counts, extra_mask=soft, spread=spread,
                       cursor=cursor)
        st = crm.snapshot()
        mask = st.node_mask if soft is None else st.node_mask & soft
        want, want_orders, want_cursor, want_av = _per_task(
            st.totals, st.avail, mask, g_vecs, g_counts, spread, cursor)
        host = _host_twin(st.totals, st.avail, mask, g_vecs, g_counts,
                          spread, cursor)
        np.testing.assert_array_equal(host[0], want)
        assert host[1] == want_orders
        assert host[2] == want_cursor
        np.testing.assert_array_equal(host[3], want_av)

        np.testing.assert_array_equal(got, want)
        orders, end = _device_orders(eng, got, spread, cursor)
        assert orders == want_orders
        assert end == want_cursor
        budgets = np.stack([eng.budget_row_host(v) for v in vecs])
        np.testing.assert_array_equal(
            budgets, compute_budgets(st.totals, want_av, vecs,
                                     node_mask=mask))
        cursor = want_cursor            # carried into the next beat
    assert eng.stats["spread_groups"] - stats0["spread_groups"] == 3 * k
    n = crm.snapshot().num_nodes
    spread_rows = [r for rows in want_orders.values() for r in rows]
    assert spread_rows and max(spread_rows) < n
    if case == "exhausts_into_feasible":
        # some SPREAD group ran past every available slot
        assert eng.last_spread()[0].sum() > 0
    if case == "masked_rows":
        assert not set(spread_rows) & {4, 12}       # the soft mask
    if case == "infeasible_class":
        rows = [rows for g, rows in want_orders.items()
                if (g_vecs[g] == vecs[-1]).all()][0]
        assert rows == [-1] * len(rows)


def test_feasible_phase_queues_round_robin_without_a_debit():
    # rows 0 and 3 fit one task each, row 1 none, row 2 is masked out:
    # from cursor 3 the first two tasks take 3 then 0, the other five go
    # round-robin over the feasible rows 0, 1, 3 from cursor 1, no debit
    totals = np.array([[400, 0], [400, 0], [100, 0], [400, 0]])
    avail = np.array([[200, 0], [100, 0], [100, 0], [200, 0]])
    mask = np.array([True, True, False, True])
    req = np.array([200, 0])
    row, queued, after, cursor = spread_group_host(avail, totals, mask, req,
                                                   7, 3)
    assert row.tolist() == [2, 2, 0, 3, 0]
    assert queued.tolist() == [1, 2, 0, 2]
    np.testing.assert_array_equal(after[:, 0], [0, 100, 100, 0])
    assert cursor == 0
    seq = [3, 0, 1, 3, 0, 1, 3]
    assert _spread_order(row, queued, 3, 4).tolist() == seq
    want = _per_task(totals, avail, mask, [req], [7], [True], 3)
    assert want[1][0] == seq and want[2] == 0


CLASSES = [{"CPU": 1}, {"CPU": 2, "memory": 1}, {"CPU": 1, "memory": 4},
           {"CPU": 3}]


@pytest.fixture
def cluster():
    from ray_tpu.cluster_utils import Cluster
    from ray_tpu.common.config import Config
    Config.reset({"health_check_period_ms": 3_600_000})
    c = Cluster()
    try:
        c.add_node(resources={"CPU": 8, "memory": 16}, num_workers=0)
        for i in range(10):
            c.add_node(resources={"CPU": 3 + i % 4, "memory": 4 + 2 * i},
                       num_workers=0)
        yield c
    finally:
        c.stop()


def _specs(rng, n, spread_share=0.75, args=()):
    """``n`` TaskSpecs in a shuffled order: SPREAD ones over classes 0-1
    (one shared strategy object, as one ``.options()`` call builds),
    DEFAULT ones over classes 2-3."""
    from ray_tpu.common.ids import JobID, TaskID
    from ray_tpu.common.resources import ResourceRequest
    from ray_tpu.common.task_spec import TaskSpec, TaskType
    from ray_tpu.util.scheduling_strategies import resolve_strategy
    spread = resolve_strategy("SPREAD")
    job = JobID.from_int(1)
    out = []
    for t in range(n):
        sp = rng.random() < spread_share
        c = int(rng.integers(0, 2)) + (0 if sp else 2)
        out.append(TaskSpec(
            task_id=TaskID.for_task(job), job_id=job,
            task_type=TaskType.NORMAL_TASK, function_descriptor="spread:task",
            args=args if sp else (), resources=ResourceRequest(CLASSES[c]),
            strategy=spread if sp else resolve_strategy("DEFAULT")))
    return out


def _rounds(raylet, batch, floor, n_rounds=2, backend=True):
    """``n_rounds`` rounds of ``batch`` from cursor 0: (rows, cursor
    after) of each."""
    from ray_tpu.common.config import get_config
    cfg = get_config()
    cfg.scheduler_device_batch_min = floor
    cfg.scheduler_device_backend = backend
    raylet._policy.spread.cursor = 0
    out = []
    for _ in range(n_rounds):
        rows = raylet._schedule_rows(batch)
        out.append((rows, raylet._policy.spread.cursor))
    cfg.scheduler_device_backend = True
    return out


def _by_class(specs, rows, kind):
    """Each class's rows, sorted, over the specs of strategy ``kind``."""
    out: dict = {}
    for s, r in zip(specs, rows):
        if s.strategy.kind is kind:
            out.setdefault(s.resources.key(), []).append(r)
    return {k: sorted(v) for k, v in out.items()}


def test_raylet_spread_rounds_agree_on_every_path(cluster):
    from ray_tpu.common.task_spec import SchedulingStrategyKind as Kind
    from ray_tpu.runtime.task_manager import TaskRecord
    raylet = cluster.head()
    specs = _specs(np.random.default_rng(3), 300)
    batch = [TaskRecord(s, 0, []) for s in specs]
    before = raylet.queue_stats()
    per_task = _rounds(raylet, batch, 1, backend=False)
    counted = raylet.queue_stats()
    assert counted["per_task_rounds"] - before["per_task_rounds"] == 2
    assert raylet._delta_engine is None
    host = _rounds(raylet, batch, 10**9)
    assert raylet._delta_engine is None
    device = _rounds(raylet, batch, 1)
    eng = raylet._delta_engine
    assert eng.stats["beats"] == 2 and eng.stats["spread_groups"] == 4
    assert raylet.queue_stats()["per_task_rounds"] == \
        counted["per_task_rounds"]
    assert host == device
    for (pt_rows, pt_cur), (rows, cur) in zip(per_task, device):
        assert cur == pt_cur
        spread = [i for i, s in enumerate(specs)
                  if s.strategy.kind is Kind.SPREAD]
        assert [rows[i] for i in spread] == [pt_rows[i] for i in spread]
        # DEFAULT tasks of one class are interchangeable
        assert _by_class(specs, rows, Kind.DEFAULT) == _by_class(
            specs, pt_rows, Kind.DEFAULT)
    # the rounds rotate: the second starts where the first left off
    assert per_task[0][1] != 0 and per_task[0][0] != per_task[1][0]


def test_spread_spec_takes_no_probe_row_and_keeps_its_flag(cluster):
    from ray_tpu.common.ids import ObjectID
    from ray_tpu.runtime.object_ref import ObjectRef
    from ray_tpu.runtime.task_manager import TaskRecord
    raylet = cluster.head()
    oid = ObjectID.from_random()
    raylet.store.put_remote(oid, 1 << 20)
    cluster.directory.add_location(oid, 4)
    specs = _specs(np.random.default_rng(5), 64, args=(ObjectRef(oid),))
    spread = [s for s in specs if s.args]
    flagged = {spread[0].task_id, spread[3].task_id}
    with raylet._cv:
        raylet._avoid_local.update(flagged)
    locs = raylet._locality_rows(specs)
    assert not {id(s) for s in spread} & set(locs)
    before = raylet.queue_stats()
    sub = raylet._subgroup(specs, locs)
    assert raylet.queue_stats()["group_probed"] == before["group_probed"]
    assert all(p == -1 for p in sub.prefs) and not any(sub.avoids)
    assert sum(sub.spread) == 2
    batch = [TaskRecord(s, 0, []) for s in specs]
    device = _rounds(raylet, batch, 1, n_rounds=1)
    host = _rounds(raylet, batch, 10**9, n_rounds=1)
    assert device == host
    assert raylet._avoid_local == flagged     # flags left in place
    assert raylet.queue_stats()["locality_args"] == 0


def test_sharded_engine_sends_spread_rounds_to_the_host_twin(cluster):
    from ray_tpu.common.config import get_config
    from ray_tpu.runtime.task_manager import TaskRecord
    from ray_tpu.scheduling.sharded_delta import ShardedDeltaScheduler
    raylet = cluster.head()
    specs = _specs(np.random.default_rng(9), 200)
    batch = [TaskRecord(s, 0, []) for s in specs]
    host = _rounds(raylet, batch, 10**9)
    get_config().scheduler_shards = 2
    before = raylet.queue_stats()["spread_host_rounds"]
    sharded = _rounds(raylet, batch, 1)
    assert isinstance(raylet._delta_engine, ShardedDeltaScheduler)
    assert raylet._delta_engine.stats["beats"] == 0
    assert raylet.queue_stats()["spread_host_rounds"] - before == 2
    assert sharded == host
    # a round with no SPREAD spec still takes the sharded beat
    plain = _specs(np.random.default_rng(10), 64, spread_share=0.0)
    raylet._schedule_rows([TaskRecord(s, 0, []) for s in plain])
    assert raylet._delta_engine.stats["beats"] == 1
    with pytest.raises(NotImplementedError):
        raylet._delta_engine.beat(np.ones((1, 2), np.int32), [3],
                                  spread=[True])


def test_strategy_mixes_in_one_bucket_share_one_program(cluster):
    from ray_tpu.common.resources import ResourceRequest
    from ray_tpu.runtime.task_manager import TaskRecord
    raylet = cluster.head()
    rng = np.random.default_rng(13)

    def round_(spread_share):
        specs = _specs(rng, 96, spread_share)
        raylet._schedule_rows([TaskRecord(s, 0, []) for s in specs])

    _rounds(raylet, [], 1, n_rounds=0)      # device floor 1
    hk.fused_beat.clear_cache()
    round_(0.75)
    warm = hk.fused_beat._cache_size()
    for share in (0.2, 0.5, 0.95, 0.75):
        raylet.crm.force_subtract(int(rng.integers(0, 11)),
                                  ResourceRequest({"CPU": 1}))
        round_(share)
    assert hk.fused_beat._cache_size() == warm
    assert raylet._delta_engine.stats["spread_groups"] == 10
