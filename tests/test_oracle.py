"""Semantics tests for the CPU oracle scheduler (the parity anchor).

Scenario tests mirror the reference's C++ unit style
(hybrid_scheduling_policy_test.cc / cluster_resource_scheduler_test.cc per
SURVEY.md §4): construct synthetic NodeResources, assert the chosen node.
"""

import numpy as np
import pytest

from conftest import random_cluster, random_requests
from ray_tpu.scheduling import (ClusterState, SchedulingOptions,
                                SchedulingType, CompositeSchedulingPolicy,
                                HybridSchedulingPolicy, compute_keys,
                                expand_group_counts, group_requests,
                                schedule_grouped_oracle, schedule_one,
                                schedule_tasks, threshold_fp, unpack_key,
                                INFEASIBLE_KEY)


def cu(units):
    return int(units * 100)


def state_of(*nodes):
    """nodes: list of (total_units, avail_units) single-resource rows."""
    totals = np.array([[cu(t)] for t, _ in nodes], dtype=np.int32)
    avail = np.array([[cu(a)] for _, a in nodes], dtype=np.int32)
    return ClusterState(totals, avail)


class TestHybridSemantics:
    def test_packs_below_threshold(self):
        # Both nodes under 50% after placement -> tie at eff 0 -> first row.
        st = state_of((8, 8), (8, 8))
        req = np.array([cu(1)], dtype=np.int32)
        assert schedule_one(st, req, threshold_fp(0.5)) == 0
        # and keeps packing node 0 until it would cross the threshold
        for _ in range(2):
            assert schedule_one(st, req, threshold_fp(0.5)) == 0
        assert st.avail[0, 0] == cu(5)

    def test_spreads_above_threshold(self):
        # Node0 at 60% after placement (above thr), node1 at 30%: spread.
        st = state_of((10, 5), (10, 8))
        req = np.array([cu(1)], dtype=np.int32)
        # node0 score: (5+1)/10 = 0.6 > 0.5; node1: (2+1)/10=0.3 < 0.5 -> eff 0
        assert schedule_one(st, req, threshold_fp(0.5)) == 1

    def test_threshold_zero_always_ranks_by_score(self):
        st = state_of((10, 9), (10, 10))
        req = np.array([cu(1)], dtype=np.int32)
        # thr=0: scores 0.2 vs 0.1 -> node1 despite traversal order
        assert schedule_one(st, req, threshold_fp(0.0)) == 1

    def test_feasible_but_unavailable_queues_without_consuming(self):
        st = state_of((4, 0.5), (2, 0.25))
        req = np.array([cu(1)], dtype=np.int32)
        node = schedule_one(st, req, threshold_fp(0.5))
        assert node in (0, 1)
        # nothing consumed
        assert st.avail[0, 0] == cu(0.5) and st.avail[1, 0] == cu(0.25)

    def test_infeasible(self):
        st = state_of((4, 4))
        req = np.array([cu(8)], dtype=np.int32)
        assert schedule_one(st, req, threshold_fp(0.5)) == -1

    def test_missing_resource_is_infeasible(self):
        totals = np.array([[cu(4), 0], [cu(4), cu(1)]], dtype=np.int32)
        st = ClusterState(totals, totals.copy())
        req = np.array([cu(1), cu(1)], dtype=np.int32)
        assert schedule_one(st, req, threshold_fp(0.5)) == 1

    def test_empty_request_goes_to_first_node(self):
        st = state_of((4, 0), (4, 4))
        req = np.array([0], dtype=np.int32)
        assert schedule_one(st, req, threshold_fp(0.5)) == 0

    def test_critical_resource_is_max_over_requested(self):
        # node0: CPU util (2+1)/4=0.75, mem (1+1)/8=0.25 -> score 0.75
        # node1: CPU util (1+1)/4=0.5, mem (6+1)/8=0.875 -> score 0.875
        totals = np.array([[cu(4), cu(8)], [cu(4), cu(8)]], dtype=np.int32)
        avail = np.array([[cu(2), cu(7)], [cu(3), cu(2)]], dtype=np.int32)
        st = ClusterState(totals, avail)
        req = np.array([cu(1), cu(1)], dtype=np.int32)
        assert schedule_one(st, req, threshold_fp(0.0)) == 0

    def test_node_mask_excludes(self):
        st = state_of((8, 8), (8, 8))
        req = np.array([cu(1)], dtype=np.int32)
        mask = np.array([False, True])
        assert schedule_one(st, req, threshold_fp(0.5), mask) == 1

    def test_key_unpack(self):
        st = state_of((10, 4))
        req = np.array([cu(1)], dtype=np.int32)
        keys = compute_keys(st.totals, st.avail, req, threshold_fp(0.5))
        bucket, eff, trav = unpack_key(keys[0])
        assert bucket == 0 and trav == 0
        # score = (6+1)*4096//10 in cu terms: ((600+100)*4096)//1000
        assert eff == ((cu(6) + cu(1)) * 4096) // cu(10)


class TestSequentialBatch:
    def test_fills_then_moves_on(self):
        # capacity 2 tasks/node at 1 CPU; threshold 1.0 => pure packing
        st = state_of((2, 2), (2, 2), (2, 2))
        reqs = np.tile(np.array([[cu(1)]], dtype=np.int32), (6, 1))
        placements = schedule_tasks(st, reqs, spread_threshold=1.01)
        assert placements.tolist() == [0, 0, 1, 1, 2, 2]

    def test_spread_when_above_threshold(self):
        st = state_of((4, 4), (4, 4))
        reqs = np.tile(np.array([[cu(1)]], dtype=np.int32), (4, 1))
        # thr 0: rank by score -> alternate nodes
        placements = schedule_tasks(st, reqs, spread_threshold=0.0)
        assert placements.tolist() == [0, 1, 0, 1]

    def test_overflow_queues_on_best_feasible(self):
        st = state_of((2, 1), (4, 1))
        reqs = np.tile(np.array([[cu(1)]], dtype=np.int32), (5, 1))
        placements = schedule_tasks(st, reqs, spread_threshold=0.5)
        # 2 fit (one per node); remaining 3 queue on one feasible node
        assert (placements >= 0).all()
        tail = placements[2:]
        assert len(set(tail.tolist())) == 1


class TestGroupedOracle:
    @pytest.mark.parametrize("seed", range(5))
    @pytest.mark.parametrize("thr", [0.0, 0.5, 1.01])
    def test_grouped_equals_sequential_on_grouped_order(self, seed, thr):
        rng = np.random.default_rng(seed)
        st = random_cluster(rng, n_nodes=17, n_resources=4)
        reqs = random_requests(rng, n_tasks=200, n_resources=4, n_classes=6)
        group_reqs, group_counts, task_group = group_requests(reqs)

        # sequential loop over the grouped order
        st_a = st.copy()
        seq_reqs = np.concatenate([
            np.tile(group_reqs[g], (int(group_counts[g]), 1))
            for g in range(group_reqs.shape[0])])
        seq = schedule_tasks(st_a, seq_reqs, spread_threshold=thr)

        # grouped oracle counts
        st_b = st.copy()
        counts = schedule_grouped_oracle(st_b, group_reqs, group_counts,
                                         spread_threshold=thr)
        np.testing.assert_array_equal(st_a.avail, st_b.avail)
        # per-group histogram must match
        n = st.num_nodes
        off = 0
        for g in range(group_reqs.shape[0]):
            c = int(group_counts[g])
            hist = np.bincount(np.where(seq[off:off + c] < 0, n,
                                        seq[off:off + c]), minlength=n + 1)
            np.testing.assert_array_equal(hist, counts[g])
            off += c

    def test_expand_counts(self):
        counts = np.array([[2, 0, 1], [0, 1, 0]], dtype=np.int32)  # N=2
        task_group = np.array([0, 0, 0, 1], dtype=np.int32)
        out = expand_group_counts(counts, task_group)
        assert out.tolist() == [0, 0, -1, 1]


class TestPolicies:
    def test_spread_round_robins(self):
        policy = CompositeSchedulingPolicy()
        st = state_of((8, 8), (8, 8), (8, 8))
        req = np.array([cu(1)], dtype=np.int32)
        opts = SchedulingOptions(scheduling_type=SchedulingType.SPREAD)
        got = [policy.schedule(st, req, opts) for _ in range(6)]
        assert got == [0, 1, 2, 0, 1, 2]

    def test_node_affinity_hard_and_soft(self):
        policy = CompositeSchedulingPolicy()
        st = state_of((8, 8), (8, 8))
        req = np.array([cu(16)], dtype=np.int32)
        hard = SchedulingOptions(
            scheduling_type=SchedulingType.NODE_AFFINITY, node_row=1)
        assert policy.schedule(st, req, hard) == -1
        req2 = np.array([cu(1)], dtype=np.int32)
        assert policy.schedule(st, req2, hard) == 1
        soft = SchedulingOptions(
            scheduling_type=SchedulingType.NODE_AFFINITY, node_row=5,
            soft=True)
        assert policy.schedule(st, req2, soft) == 0

    def test_random_is_deterministic_per_seed(self):
        st = state_of((8, 8), (8, 8), (8, 8), (8, 8))
        req = np.array([cu(1)], dtype=np.int32)
        opts = SchedulingOptions(scheduling_type=SchedulingType.RANDOM)
        a = [CompositeSchedulingPolicy(seed=7).schedule(st.copy(), req, opts)
             for _ in range(3)]
        b = [CompositeSchedulingPolicy(seed=7).schedule(st.copy(), req, opts)
             for _ in range(3)]
        assert a == b

    def test_hybrid_require_available(self):
        policy = HybridSchedulingPolicy()
        st = state_of((4, 0.5))
        req = np.array([cu(1)], dtype=np.int32)
        opts = SchedulingOptions(require_node_available=True)
        assert policy.schedule(st, req, opts) == -1

def _churn_cluster(seed, n_nodes=24, n_classes=6, capacity=32):
    """A live ClusterResourceManager + interned class batch for the
    delta-sequence tests (the real mutation surface, not a synthetic
    snapshot)."""
    from ray_tpu.common.ids import NodeID
    from ray_tpu.common.resources import NodeResources, ResourceRequest
    from ray_tpu.scheduling import ClusterResourceManager

    rng = np.random.default_rng(seed)
    crm = ClusterResourceManager(capacity=capacity)
    ids = [crm.id_of(crm.add_node(NodeID.from_random(), NodeResources(
        {"CPU": int(rng.integers(2, 32)),
         "memory": int(rng.integers(1, 64))})))
        for _ in range(n_nodes)]
    class_reqs = [ResourceRequest({"CPU": int(rng.integers(1, 4)),
                                   "memory": float(rng.integers(0, 6))})
                  for _ in range(n_classes)]
    vecs = np.stack([crm.intern_request(r) for r in class_reqs])
    counts = rng.integers(1, 12, size=n_classes).astype(np.int32)
    return rng, crm, ids, vecs, counts


def _mutate(rng, crm, node_ids, debts):
    """One beat's worth of random CRM churn: subtract / add_back /
    drain / suspect / heartbeat-avail updates (>=1 mutation so delta
    beats actually occur at every seed)."""
    from ray_tpu.common.resources import ResourceRequest
    one = ResourceRequest({"CPU": 1})
    for _ in range(1 + int(rng.integers(0, 5))):
        op = int(rng.integers(0, 5))
        row = int(rng.integers(0, len(node_ids)))
        if op == 0:
            crm.force_subtract(row, one)
            debts.append(row)
        elif op == 1 and debts:
            crm.add_back(debts.pop(int(rng.integers(0, len(debts)))), one)
        elif op == 2:
            crm.set_draining(node_ids[row], bool(rng.integers(0, 2)))
        elif op == 3:
            crm.set_suspect(row, bool(rng.integers(0, 2)))
        else:
            crm.update_node_available(
                node_ids[row], {"CPU": int(rng.integers(0, 3200))})


class TestDeltaSequenceOracle:
    """Randomized delta-sequence parity (the r08 tentpole gate): a
    DeltaScheduler fed random CRM mutations between beats stays
    bit-identical, every beat, to (a) the CPU grouped oracle on a fresh
    snapshot and (b) a cold engine that full-rescores from scratch —
    and its carried key tensor matches ``contract.compute_keys``.
    Seeded and replayable."""

    @pytest.mark.parametrize("seed", [0, 1, 2, 7])
    def test_random_mutation_sequence_bit_exact(self, seed):
        from ray_tpu.scheduling import DeltaScheduler

        rng, crm, ids, vecs, counts = _churn_cluster(seed)
        eng = DeltaScheduler(crm)
        debts = []
        thr = threshold_fp(None)
        for _ in range(10):
            _mutate(rng, crm, ids, debts)
            got = eng.beat(vecs, counts)
            want = schedule_grouped_oracle(crm.snapshot(), vecs, counts)
            np.testing.assert_array_equal(got, want)
            cold = DeltaScheduler(crm)
            np.testing.assert_array_equal(cold.beat(vecs, counts), want)
            st = crm.snapshot()
            from ray_tpu.scheduling import compute_keys_batch
            np.testing.assert_array_equal(
                np.stack([eng.keys_row_host(v) for v in vecs]),
                compute_keys_batch(st.totals, st.avail, vecs, thr,
                                   st.node_mask))
        assert eng.stats["delta_beats"] > 0
        assert eng.hit_rate() > 0

    def test_dirty_fraction_fallback_knob(self):
        """scheduler_delta_max_dirty_fraction = 0 forces a full rescore
        on every dirty beat — parity holds, hit rate records it."""
        from ray_tpu.common.config import Config
        from ray_tpu.scheduling import DeltaScheduler

        try:
            Config.reset({"scheduler_delta_max_dirty_fraction": 0.0})
            rng, crm, ids, vecs, counts = _churn_cluster(3)
            eng = DeltaScheduler(crm)
            debts = []
            for _ in range(5):
                _mutate(rng, crm, ids, debts)
                np.testing.assert_array_equal(
                    eng.beat(vecs, counts),
                    schedule_grouped_oracle(crm.snapshot(), vecs, counts))
            assert eng.stats["delta_beats"] == 0
            assert eng.stats["full_rescores"] == eng.stats["beats"]
            assert eng.hit_rate() == 0.0
        finally:
            Config.reset()

    def test_overrides_and_softmask_match_effective_snapshot(self):
        """Per-beat avail overrides (planned-load debits) and the
        suspect soft mask reproduce the snapshot path's arithmetic
        bit-for-bit."""
        from ray_tpu.scheduling import DeltaScheduler

        rng, crm, ids, vecs, counts = _churn_cluster(5)
        eng = DeltaScheduler(crm)
        eng.beat(vecs, counts)                  # warm sync
        # planned-load debit on two rows + suspect row 1
        over = {}
        for row in (0, 1):
            base = crm.arrays()[1][row].astype(np.int64)
            base -= 150
            over[row] = base.clip(-(2 ** 30), 2 ** 30).astype(np.int32)
        sus = np.ones(crm.arrays()[0].shape[0], bool)
        sus[1] = False
        got = eng.beat(vecs, counts, overrides=over, extra_mask=sus)
        st = crm.snapshot()
        for row in (0, 1):
            st.avail[row] = over[row]
        st.node_mask = st.node_mask & sus       # frozen mask: rebind
        np.testing.assert_array_equal(
            got, schedule_grouped_oracle(st, vecs, counts))

    def test_structural_growth_forces_resync(self):
        """Node-capacity growth moves arrays under the mirror: the
        journal truncates and the next beat full-rescores, bit-exact."""
        from ray_tpu.common.ids import NodeID
        from ray_tpu.common.resources import NodeResources
        from ray_tpu.scheduling import DeltaScheduler

        rng, crm, ids, vecs, counts = _churn_cluster(11, capacity=24)
        eng = DeltaScheduler(crm)
        eng.beat(vecs, counts)
        before = eng.stats["full_rescores"]
        for _ in range(10):                     # outgrow capacity=24
            crm.add_node(NodeID.from_random(),
                         NodeResources({"CPU": 8}))
        got = eng.beat(vecs, counts)
        assert eng.stats["full_rescores"] == before + 1
        np.testing.assert_array_equal(
            got, schedule_grouped_oracle(crm.snapshot(), vecs, counts))

    def test_class_retire_and_reuse(self):
        """Retiring an interned class frees its slot; a new class takes
        it over and scores correctly."""
        from ray_tpu.common.resources import ResourceRequest
        from ray_tpu.scheduling import DeltaScheduler

        rng, crm, ids, vecs, counts = _churn_cluster(13)
        eng = DeltaScheduler(crm)
        eng.beat(vecs, counts)
        assert eng.retire_class(vecs[0])
        assert not eng.retire_class(vecs[0])    # already gone
        nv = crm.intern_request(ResourceRequest({"CPU": 2.5}))
        got = eng.beat(np.stack([nv]), np.array([4], np.int32))
        np.testing.assert_array_equal(
            got, schedule_grouped_oracle(
                crm.snapshot(), np.stack([nv]), np.array([4], np.int32)))


class TestCrmEpochViews:
    """Epoch counter, dirty journal, and memoized frozen views on the
    ClusterResourceManager (r08 satellite)."""

    def _crm(self, n=4):
        from ray_tpu.common.ids import NodeID
        from ray_tpu.common.resources import NodeResources
        from ray_tpu.scheduling import ClusterResourceManager
        crm = ClusterResourceManager(capacity=8)
        rows = [crm.add_node(NodeID.from_random(),
                             NodeResources({"CPU": 8}))
                for _ in range(n)]
        return crm, rows

    def test_mutations_bump_epoch_and_journal_rows(self):
        from ray_tpu.common.resources import ResourceRequest
        crm, rows = self._crm()
        v0 = crm.version
        crm.force_subtract(rows[2], ResourceRequest({"CPU": 1}))
        v, _t, _a, _m, dirty = crm.delta_view(v0)
        assert v > v0 and dirty == {rows[2]}
        # a consumer synced at v sees a clean view
        assert crm.delta_view(v)[4] == set()

    def test_struct_growth_reports_full_resync(self):
        from ray_tpu.common.ids import NodeID
        from ray_tpu.common.resources import NodeResources
        crm, rows = self._crm()
        v0 = crm.version
        for _ in range(8):                      # outgrow capacity=8
            crm.add_node(NodeID.from_random(),
                         NodeResources({"CPU": 4}))
        assert crm.delta_view(v0)[4] is None    # None = resync required

    def test_frozen_views_memoized_by_epoch(self):
        from ray_tpu.common.resources import ResourceRequest
        crm, rows = self._crm()
        t1 = crm.arrays()[0]
        assert crm.arrays()[0] is t1            # same epoch: same object
        assert not t1.flags.writeable
        crm.force_subtract(rows[0], ResourceRequest({"CPU": 1}))
        assert crm.arrays()[0] is not t1        # epoch moved: fresh copy
        # snapshot avail stays per-call writable (policies mutate it)
        snap = crm.snapshot()
        assert snap.avail.flags.writeable
        snap2 = crm.snapshot()
        assert snap.avail is not snap2.avail

    def test_request_vectors_interned_once(self):
        from ray_tpu.common.resources import ResourceRequest
        crm, rows = self._crm()
        a = crm.intern_request(ResourceRequest({"CPU": 2}))
        b = crm.intern_request(ResourceRequest({"CPU": 2}))
        assert a is b and not a.flags.writeable
        c = crm.intern_request(ResourceRequest({"CPU": 3}))
        assert c is not a


class TestShardedDeltaSequenceOracle:
    """Randomized delta-sequence parity for the mesh-sharded engine
    (r14 tentpole gate): a ShardedDeltaScheduler at 2/4/8 shards fed
    the SAME random CRM mutation stream stays bit-identical, every
    beat, to the single-device DeltaScheduler and to the CPU grouped
    oracle on a fresh snapshot.  conftest pins 8 virtual CPU devices,
    so every shard count here runs in tier-1."""

    @pytest.mark.parametrize("shards,n_nodes,capacity", [
        pytest.param(2, 24, 32, id="2"), pytest.param(4, 24, 32, id="4"),
        pytest.param(8, 24, 32, id="8"),
        # 27 CRM rows: no shard count divides the node axis
        pytest.param(2, 27, 27, id="2-uneven"),
        pytest.param(8, 27, 27, id="8-uneven")])
    def test_sharded_matches_single_device_and_oracle(self, shards,
                                                      n_nodes, capacity):
        from ray_tpu.scheduling import DeltaScheduler, ShardedDeltaScheduler

        rng, crm, ids, vecs, counts = _churn_cluster(
            seed=shards, n_nodes=n_nodes, capacity=capacity)
        eng = ShardedDeltaScheduler(crm, shards)
        ref = DeltaScheduler(crm)
        assert eng.stats["shards"] == shards
        debts = []
        for _ in range(8):
            _mutate(rng, crm, ids, debts)
            got = eng.beat(vecs, counts)
            np.testing.assert_array_equal(got, ref.beat(vecs, counts))
            np.testing.assert_array_equal(
                got, schedule_grouped_oracle(crm.snapshot(), vecs, counts))
        assert eng.stats["delta_beats"] > 0

    @pytest.mark.parametrize("shards", [2, 4])
    def test_structural_growth_rebalances_shards(self, shards):
        """Capacity growth moves the node axis under the shards: the
        next beat re-pads, re-shards, and full-rescores — bit-exact
        before AND after the re-balance."""
        from ray_tpu.common.ids import NodeID
        from ray_tpu.common.resources import NodeResources
        from ray_tpu.scheduling import ShardedDeltaScheduler

        rng, crm, ids, vecs, counts = _churn_cluster(17, capacity=24)
        eng = ShardedDeltaScheduler(crm, shards)
        debts = []
        for grow in (False, True, False, True, False):
            if grow:                            # outgrow capacity=24
                for _ in range(40):
                    ids.append(crm.id_of(crm.add_node(
                        NodeID.from_random(),
                        NodeResources({"CPU": int(rng.integers(2, 32))}))))
            _mutate(rng, crm, ids, debts)
            np.testing.assert_array_equal(
                eng.beat(vecs, counts),
                schedule_grouped_oracle(crm.snapshot(), vecs, counts))
        assert eng.stats["full_rescores"] >= 2

    def test_shard_count_one_degenerate(self):
        """shards=1 is a (1, 1) mesh — the sharded code path with no
        partner to reduce with — and must stay bit-exact too."""
        from ray_tpu.scheduling import ShardedDeltaScheduler

        rng, crm, ids, vecs, counts = _churn_cluster(19)
        eng = ShardedDeltaScheduler(crm, 1)
        assert eng.stats["shards"] == 1
        debts = []
        for _ in range(5):
            _mutate(rng, crm, ids, debts)
            np.testing.assert_array_equal(
                eng.beat(vecs, counts),
                schedule_grouped_oracle(crm.snapshot(), vecs, counts))

    def test_factory_resolves_knobs(self):
        """make_delta_scheduler: default knob (1 shard) falls back to
        the single-device engine; 0 = one shard per local device;
        non-power-of-two requests round down."""
        from ray_tpu.scheduling import (DeltaScheduler,
                                        ShardedDeltaScheduler,
                                        make_delta_scheduler)

        _rng, crm, _ids, _v, _c = _churn_cluster(23)
        assert type(make_delta_scheduler(crm)) is DeltaScheduler
        auto = make_delta_scheduler(crm, n_shards=0)
        assert isinstance(auto, ShardedDeltaScheduler)
        assert auto.stats["shards"] == 8        # conftest pins 8 devices
        assert make_delta_scheduler(crm, n_shards=5).stats["shards"] == 4

    def test_sharded_overrides_and_softmask(self):
        """Planned-load overrides + the suspect soft mask land on the
        right shards (global row -> owning device's local bucket)."""
        from ray_tpu.scheduling import DeltaScheduler, ShardedDeltaScheduler

        rng, crm, ids, vecs, counts = _churn_cluster(29)
        eng = ShardedDeltaScheduler(crm, 4)
        ref = DeltaScheduler(crm)
        eng.beat(vecs, counts)
        ref.beat(vecs, counts)
        n_rows = crm.arrays()[0].shape[0]
        over = {row: crm.arrays()[1][row] - np.int32(150)
                for row in (0, 7, 15, 23)}
        sus = np.ones(n_rows, bool)
        sus[[1, 9]] = False
        for ra in (False, True):
            np.testing.assert_array_equal(
                eng.beat(vecs, counts, overrides=over, extra_mask=sus,
                         require_available=ra),
                ref.beat(vecs, counts, overrides=over, extra_mask=sus,
                         require_available=ra))


class TestFrozenCacheRecycle:
    """r14 satellite: the epoch-memoized frozen views recycle the
    retired generation by patching only the dirtied rows instead of
    re-copying every shard's rows on each resync — without ever
    mutating a view some consumer still holds."""

    def _crm(self, n=16):
        from ray_tpu.common.ids import NodeID
        from ray_tpu.common.resources import NodeResources
        from ray_tpu.scheduling import ClusterResourceManager
        crm = ClusterResourceManager(capacity=32)
        rows = [crm.add_node(NodeID.from_random(),
                             NodeResources({"CPU": 8}))
                for _ in range(n)]
        return crm, rows

    def test_single_dirty_row_patches_not_rebuilds(self):
        from ray_tpu.common.resources import ResourceRequest
        crm, rows = self._crm()
        req = ResourceRequest({"CPU": 1})
        crm.arrays()
        crm.force_subtract(rows[0], req)
        crm.arrays()                            # both generations exist
        base = dict(crm.frozen_stats)
        for i in range(6):
            crm.force_subtract(rows[i % 16], req)
            crm.arrays()
        d = {k: crm.frozen_stats[k] - base[k] for k in base}
        assert d["full"] == 0 and d["patched"] == 6
        # each patch covers the rows dirtied across TWO epochs (the
        # retired generation is two beats old), never the whole table
        assert d["rows_patched"] <= 2 * d["patched"]

    def test_patched_views_bit_exact_under_churn(self):
        from ray_tpu.common.resources import ResourceRequest
        crm, rows = self._crm()
        rng = np.random.default_rng(0)
        req = ResourceRequest({"CPU": 1})
        for _ in range(100):
            row = rows[int(rng.integers(0, len(rows)))]
            if rng.random() < 0.2:
                crm.set_draining(crm.id_of(row), bool(rng.integers(0, 2)))
            else:
                crm.force_subtract(row, req)
            _v, t, a, m, _rows = crm.delta_view(-2)
            np.testing.assert_array_equal(t, crm.totals)
            np.testing.assert_array_equal(a, crm.avail)
            np.testing.assert_array_equal(
                m, crm.node_mask & ~crm.draining)
        assert crm.frozen_stats["patched"] > 50

    def test_held_view_forces_full_copy(self):
        """The immutability contract survives recycling: while any
        consumer holds a frozen array, its generation is never patched
        in place — a fresh copy is built instead."""
        from ray_tpu.common.resources import ResourceRequest
        crm, rows = self._crm()
        req = ResourceRequest({"CPU": 1})
        crm.arrays()
        crm.force_subtract(rows[0], req)
        held = crm.arrays()                     # hold gen 2's arrays
        t_held = held[0].copy()
        crm.force_subtract(rows[1], req)
        crm.arrays()
        crm.force_subtract(rows[2], req)
        t_new = crm.arrays()[0]
        assert t_new is not held[0]
        assert not held[0].flags.writeable
        np.testing.assert_array_equal(held[0], t_held)   # untouched
        assert crm.frozen_stats["full"] >= 3

    def test_struct_growth_falls_back_to_full_copy(self):
        from ray_tpu.common.ids import NodeID
        from ray_tpu.common.resources import NodeResources, ResourceRequest
        crm, rows = self._crm()
        req = ResourceRequest({"CPU": 1})
        crm.arrays()
        crm.force_subtract(rows[0], req)
        crm.arrays()
        before = crm.frozen_stats["full"]
        for _ in range(20):                     # outgrow capacity=32
            crm.add_node(NodeID.from_random(), NodeResources({"CPU": 4}))
        crm.arrays()
        crm.force_subtract(rows[1], req)
        crm.arrays()                            # shapes moved: full again
        assert crm.frozen_stats["full"] >= before + 1
        np.testing.assert_array_equal(crm.arrays()[0], crm.totals)
