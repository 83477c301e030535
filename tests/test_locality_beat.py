"""Locality-biased and avoid rounds on the resident delta beat.

``fused_beat``'s localized program (per-group preferred rows, avoid
flags, the local row) against the snapshot kernel
``schedule_grouped_localized_np`` and the host twin
``schedule_group_host(pref_row=...)``; the same through
``DeltaScheduler`` and ``ShardedDeltaScheduler`` under CRM churn; a
raylet round with plasma arguments and an avoid flag taking the beat;
and a plain beat keeping its own compiled program.  Seeded throughout.
"""

import numpy as np
import pytest

from test_budget_beat import _engine
from test_oracle import _churn_cluster, _mutate
from ray_tpu.ops import hybrid_kernel as hk
from ray_tpu.ops.hybrid_kernel import schedule_group_host
from ray_tpu.ops.locality_kernel import schedule_grouped_localized_np
from ray_tpu.scheduling.contract import compute_budgets, threshold_fp

THR = threshold_fp(None)


def _host_twin(totals, avail, mask, reqs, counts, prefs, avoids,
               local_row):
    """Group by group on the host: avoid masks ``local_row`` and wins
    over a preferred row, as the raylet's host round does."""
    n = totals.shape[0]
    avail = np.asarray(avail, np.int64).copy()
    rows = []
    for req, c, p, a in zip(reqs, counts, prefs, avoids):
        gmask = np.ones(n, bool)
        if a and 0 <= local_row < n:
            gmask[local_row] = False
        row, avail = schedule_group_host(avail, totals, mask, req, c, gmask,
                                         THR, pref_row=-1 if a else p)
        rows.append(row)
    return np.stack(rows), avail


def _case(seed):
    """A cluster of 24 nodes (node 3 too small for every class), 4
    classes and 10 groups sharing them; preferred rows that are feasible,
    exhausted (a count past the row's capacity), infeasible (node 3, and
    a row the soft mask drops) and overridden by an avoid flag."""
    rng = np.random.default_rng(seed)
    n, r = 24, 2
    totals = np.stack([rng.integers(4, 33, n), rng.integers(8, 65, n)],
                      axis=1).astype(np.int32) * 100
    totals[3] = [100, 100]
    avail = (totals * rng.uniform(0.2, 1.0, totals.shape)).astype(np.int32)
    mask = rng.random(n) > 0.1
    mask[[5, 7, 9]] = True
    extra = np.ones(n, bool)
    extra[9] = False                              # suspect: soft-masked
    reqs = np.stack([[100 * int(rng.integers(2, 4)),
                      100 * int(rng.integers(0, 4))] for _ in range(4)]
                    ).astype(np.int32)
    slots = np.array([0, 1, 2, 3, 0, 1, 2, 3, 0, 1], np.int32)
    counts = rng.integers(1, 9, slots.shape[0]).astype(np.int32)
    counts[1] = 400                               # exhausts row 5
    local = 7
    prefs = np.array([-1, 5, 3, 9, 7, -1, 5, int(rng.integers(0, n)), -1,
                      7], np.int32)
    avoids = np.array([0, 0, 0, 0, 1, 1, 0, 0, 0, 1], bool)
    # per-beat overrides: two rows' avail replaced for this beat only
    ov = {5: (avail[5] // 2).astype(np.int32), 11: np.zeros(r, np.int32)}
    return totals, avail, mask, extra, reqs, slots, counts, prefs, avoids, \
        local, ov


def _pad(a, n, fill=0):
    out = np.full((n,) + a.shape[1:], fill, a.dtype)
    out[:a.shape[0]] = a
    return out


@pytest.mark.parametrize("seed", [1, 2, 3, 4])
def test_fused_beat_localized_matches_twins(seed):
    import jax
    (totals, avail, mask, extra, reqs, slots, counts, prefs, avoids, local,
     ov) = _case(seed)
    n, gp, cp, np_ = totals.shape[0], 16, 8, 32
    avail_eff = avail.copy()
    for row, vec in ov.items():
        avail_eff[row] = vec
    mask_eff = mask & extra
    g = slots.shape[0]
    want, want_av = _host_twin(totals, avail_eff, mask_eff, reqs[slots],
                               counts, prefs, avoids, local)
    gmask = np.ones((g, n), bool)
    gmask[avoids, local] = False
    snap, snap_av = schedule_grouped_localized_np(
        totals, avail_eff, mask, reqs[slots], counts,
        np.where(avoids, -1, prefs), group_masks=gmask, thr_fp=THR,
        extra_mask=extra)
    np.testing.assert_array_equal(snap, want)
    np.testing.assert_array_equal(snap_av, want_av)

    reqs_p = _pad(reqs, cp)
    keys = hk.full_rescore(_pad(totals, np_), _pad(avail, np_),
                           _pad(mask, np_, False), reqs_p, THR)
    ov_idx = np.full(8, np_, np.int32)
    ov_av = np.zeros((8, 2), np.int32)
    for j, (row, vec) in enumerate(sorted(ov.items())):
        ov_idx[j], ov_av[j] = row, vec
    packed, _ = hk.fused_beat(
        _pad(totals, np_), _pad(avail, np_), _pad(mask, np_, False), keys,
        reqs_p, _pad(slots, gp, cp), _pad(counts, gp),
        _pad(extra, np_, True), ov_idx, ov_av, THR,
        pref_rows=jax.device_put(_pad(prefs, gp, -1)),
        avoid=jax.device_put(_pad(avoids, gp, False)),
        local_row=np.int32(local))
    packed = np.asarray(packed)
    got = np.concatenate([packed[:g, :n], packed[:g, -1:]], axis=1)
    np.testing.assert_array_equal(got, want)
    # budgets off the post-beat avail, every resident class
    np.testing.assert_array_equal(
        packed[gp:gp + 4, :n],
        compute_budgets(totals, want_av, reqs, node_mask=mask_eff))
    # the case holds each kind of preferred row it names
    assert want[1, 5] == 400 and want[2, 3] == 0 and want[3, 9] == 0
    assert want[4, local] == 0 and want[9, local] == 0


@pytest.mark.parametrize("shards,n_nodes,capacity", [
    pytest.param(1, 24, 32, id="1"), pytest.param(2, 24, 32, id="2"),
    pytest.param(8, 24, 32, id="8"),
    # 27 CRM rows: no shard count divides the node axis
    pytest.param(2, 27, 27, id="2-uneven"),
    pytest.param(8, 27, 27, id="8-uneven")])
def test_delta_beat_localized_under_churn(shards, n_nodes, capacity):
    rng, crm, ids, vecs, counts = _churn_cluster(
        seed=40 + shards, n_nodes=n_nodes, capacity=capacity)
    eng = _engine(crm, shards)
    debts = []
    # groups share classes: each class once plain, once with locality
    g_vecs = np.concatenate([vecs, vecs])
    stats0 = None
    for beat in range(8):
        _mutate(rng, crm, ids, debts)
        n = crm.snapshot().totals.shape[0]
        g = g_vecs.shape[0]
        g_counts = rng.integers(1, 40, g).astype(np.int32)
        prefs = np.where(rng.random(g) < 0.6, rng.integers(0, n, g), -1)
        prefs[:vecs.shape[0]] = -1
        avoids = rng.random(g) < 0.25
        local = int(rng.integers(0, n))
        if beat == 0:
            eng.beat(g_vecs, g_counts)           # the full sync
            stats0 = dict(eng.stats)
            continue
        got = eng.beat(g_vecs, g_counts, prefs=prefs.tolist(),
                       avoids=avoids.tolist(), local_row=local)
        st = crm.snapshot()
        want, want_av = _host_twin(st.totals, st.avail, st.node_mask,
                                   g_vecs, g_counts, prefs, avoids, local)
        np.testing.assert_array_equal(got, want)
        budgets = np.stack([eng.budget_row_host(v) for v in vecs])
        np.testing.assert_array_equal(
            budgets, compute_budgets(st.totals, want_av, vecs,
                                     node_mask=st.node_mask))
    grew = {k: eng.stats[k] - stats0[k]
            for k in ("groups", "pref_groups", "avoid_groups")}
    assert grew["groups"] == 7 * g_vecs.shape[0]
    assert 0 < grew["pref_groups"] < grew["groups"]
    assert 0 < grew["avoid_groups"] < grew["groups"]
    assert eng.stats["delta_beats"] > 0


@pytest.mark.parametrize("shards", [1, 2])
def test_plain_beat_keeps_its_program(shards):
    rng, crm, ids, vecs, counts = _churn_cluster(seed=60 + shards)
    eng = _engine(crm, shards)
    g = len(vecs)

    def programs():
        if shards <= 1:
            return {"any": hk.fused_beat._cache_size()}
        return {k: f._cache_size() for k, f in eng._plane._fused.items()}

    def beats():
        _mutate(rng, crm, ids, [])
        eng.beat(vecs, counts)
        eng.beat(vecs, counts, prefs=[-1] * g,
                 avoids=[False] * g)              # no signal: plain
        eng.beat(vecs, counts, prefs=[0] + [-1] * (g - 1), local_row=0)
        eng.beat(vecs, counts, avoids=[True] + [False] * (g - 1),
                 local_row=1)

    if shards <= 1:
        hk.fused_beat.clear_cache()     # count from this test's programs
    eng.beat(vecs, counts)
    if shards <= 1:
        # a fresh plain beat's program, then the localized variant: one
        # more program, the plain one untouched
        plain = programs()["any"]
        eng.beat(vecs, counts, prefs=[0] + [-1] * (g - 1), local_row=0)
        assert programs()["any"] == plain + 1
    beats()
    while not eng.stats["delta_beats"]:
        beats()         # every variant warm, on synced residents too
    warm = programs()
    for _ in range(3):
        beats()
    assert programs() == warm                   # nothing compiles again
    if shards > 1:
        # plain beats keep their key, the localized variant has its own
        assert set(warm) >= {(False, False), (False, True)}
        assert not any(k[0] for k in warm)


def test_raylet_locality_round_takes_the_beat(monkeypatch):
    from ray_tpu.cluster_utils import Cluster
    from ray_tpu.common.config import Config, get_config
    from ray_tpu.common.ids import JobID, ObjectID, TaskID
    from ray_tpu.common.resources import ResourceRequest
    from ray_tpu.common.task_spec import TaskSpec, TaskType
    from ray_tpu.leasing.board import budget_board
    from ray_tpu.ops import locality_kernel
    from ray_tpu.runtime.object_ref import ObjectRef
    from ray_tpu.runtime.task_manager import TaskRecord

    Config.reset({"scheduler_device_batch_min": 256,
                  "health_check_period_ms": 3_600_000})
    cluster = Cluster()
    try:
        cluster.add_node(resources={"CPU": 8, "memory": 16}, num_workers=0)
        for i in range(11):
            cluster.add_node(resources={"CPU": 4 + i % 3, "memory": 8},
                             num_workers=0)
        raylet = cluster.head()
        rng = np.random.default_rng(11)
        refs = []
        for row in (0, 2, 5, 8):
            oid = ObjectID.from_random()
            raylet.store.put_remote(oid, int(rng.integers(1, 1 << 24)))
            cluster.directory.add_location(oid, row)
            refs.append(ObjectRef(oid))
        job = JobID.from_int(1)
        specs = []
        for t in range(320):
            k = int(rng.integers(0, 3))
            args = (refs[int(rng.integers(0, 4))],) if k else ()
            specs.append(TaskSpec(
                task_id=TaskID.for_task(job), job_id=job,
                task_type=TaskType.NORMAL_TASK,
                function_descriptor="locality:task", args=args,
                resources=ResourceRequest({"CPU": 1 + t % 2})))
        avoid = {s.task_id for s in specs[::9]}
        batch = [TaskRecord(s, 0, []) for s in specs]

        def round_(floor):
            with raylet._cv:
                raylet._avoid_local.clear()
                raylet._avoid_local.update(avoid)
            get_config().scheduler_device_batch_min = floor
            return raylet._schedule_rows(batch)

        host = round_(10**9)
        assert raylet._delta_engine is None

        def no_snapshot(*a, **kw):
            raise AssertionError("the snapshot localized kernel ran")

        monkeypatch.setattr(locality_kernel, "schedule_grouped_localized",
                            no_snapshot)
        published = []
        board = budget_board()
        monkeypatch.setattr(board, "publish",
                            lambda seq, rows: published.append(seq))
        device = round_(256)
        eng = raylet._delta_engine
        assert device == host
        assert any(r >= 0 for r in device)
        assert eng.stats["beats"] == 1
        assert eng.stats["pref_groups"] > 0 and eng.stats["avoid_groups"] > 0
        assert published == [eng.budget_seq]
    finally:
        cluster.stop()
