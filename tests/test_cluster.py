"""Simulated multi-node cluster: spillback, routing, node death, device
batch path.

Scenario sources: upstream multi-node scheduling tests against
``cluster_utils.Cluster`` (SURVEY.md §4; scenarios re-derived, not
copied)."""

import time

import pytest

import ray_tpu
from ray_tpu.cluster_utils import Cluster


@pytest.fixture(scope="module")
def cluster():
    c = Cluster()
    # head: small CPU; two workers nodes with custom resources
    c.add_node(resources={"CPU": 2, "memory": 2}, num_workers=2)
    c.add_node(resources={"CPU": 2, "memory": 2, "custom": 1},
               num_workers=2)
    c.add_node(resources={"CPU": 4, "memory": 2}, num_workers=2)
    ray_tpu.init(cluster=c)
    yield c
    ray_tpu.shutdown()
    c.stop()


@ray_tpu.remote
def whoami():
    import os
    return os.getpid()


@ray_tpu.remote
def padded(x):
    return x + 1


class TestMultiNode:
    def test_cluster_resources_aggregate(self, cluster):
        res = ray_tpu.cluster_resources()
        assert res["CPU"] == 8.0
        assert res["custom"] == 1.0
        assert len(ray_tpu.nodes()) == 3

    def test_tasks_spill_across_nodes(self, cluster):
        # 8 concurrent 1-CPU holds need all three nodes
        @ray_tpu.remote
        def hold():
            time.sleep(0.6)
            import os
            return os.getpid()

        t0 = time.time()
        pids = ray_tpu.get([hold.remote() for _ in range(8)])
        elapsed = time.time() - t0
        assert elapsed < 2.4, elapsed          # ran in parallel across nodes
        assert len(set(pids)) >= 4             # multiple worker processes

    def test_custom_resource_routes_to_owner(self, cluster):
        @ray_tpu.remote(resources={"custom": 1}, num_cpus=1)
        def custom_task():
            import os
            return os.getpid()

        # runs (only node 1 has 'custom'); infeasible elsewhere
        assert isinstance(ray_tpu.get(custom_task.remote(), timeout=30), int)

    def test_infeasible_task_parks(self, cluster):
        @ray_tpu.remote(resources={"no_such_resource": 1})
        def impossible():
            return 1

        ref = impossible.remote()
        ready, not_ready = ray_tpu.wait([ref], num_returns=1, timeout=1.0)
        assert not ready and not_ready == [ref]

    def test_actor_placement_with_resources(self, cluster):
        @ray_tpu.remote
        class Pinned:
            def where(self):
                import os
                return os.getpid()

        h = Pinned.options(resources={"custom": 1}).remote()
        assert isinstance(ray_tpu.get(h.where.remote(), timeout=30), int)
        ray_tpu.kill(h)

    def test_device_batch_path_places_all(self, cluster):
        from ray_tpu.common.config import Config
        # push the batch through the TPU/XLA kernel path
        cfg = Config.instance()
        old = cfg.scheduler_device_batch_min
        cfg.scheduler_device_batch_min = 8
        try:
            refs = [padded.remote(i) for i in range(64)]
            assert ray_tpu.get(refs, timeout=60) == [i + 1 for i in range(64)]
        finally:
            cfg.scheduler_device_batch_min = old

    def test_sharded_delta_live_path(self, cluster):
        """The live multi-chip path: with ``scheduler_shards: 0`` the
        raylet's delta engine shards over all 8 virtual devices
        (ShardedDeltaScheduler on the ShardPlane), its beats place
        every task, and a later wave rides a delta beat."""
        from ray_tpu.common.config import Config
        from ray_tpu.scheduling import ShardedDeltaScheduler
        cfg = Config.instance()
        old_min, old_shards = (cfg.scheduler_device_batch_min,
                               cfg.scheduler_shards)
        raylets = list(cluster.raylets.values())
        cfg.scheduler_device_batch_min = 8
        cfg.scheduler_shards = 0
        for r in raylets:               # engines are built lazily
            r._delta_engine = None
        try:
            engines = []
            # a wave whose rounds all fall under the batch floor takes
            # the host twin, and an engine's first beat is a full sync,
            # so waves repeat until a delta beat has run
            for _wave in range(4):
                refs = [padded.remote(i) for i in range(64)]
                assert ray_tpu.get(refs, timeout=60) == \
                    [i + 1 for i in range(64)]
                engines = [r._delta_engine for r in raylets
                           if r._delta_engine is not None]
                if any(e.stats["delta_beats"] for e in engines):
                    break
            assert engines
            for eng in engines:
                assert isinstance(eng, ShardedDeltaScheduler)
                assert eng.stats["shards"] > 1
            assert sum(e.stats["delta_beats"] for e in engines) > 0
        finally:
            cfg.scheduler_device_batch_min = old_min
            cfg.scheduler_shards = old_shards
            for r in raylets:
                r._delta_engine = None


class TestNodeArrival:
    def test_add_node_wakes_parked_infeasible_tasks(self):
        """A task parked as infeasible must run once a node with the
        required resource joins (reference: node arrival triggers
        rescheduling on every raylet)."""
        c = Cluster()
        c.add_node(resources={"CPU": 2, "memory": 2}, num_workers=2)
        ray_tpu.shutdown()
        ray_tpu.init(cluster=c)
        try:
            @ray_tpu.remote(resources={"GPU": 1})
            def needs_gpu():
                return "ran"

            ref = needs_gpu.remote()
            ready, _ = ray_tpu.wait([ref], num_returns=1, timeout=0.5)
            assert not ready                     # parked: no GPU anywhere
            c.add_node(resources={"CPU": 1, "GPU": 1}, num_workers=1)
            assert ray_tpu.get(ref, timeout=30) == "ran"
        finally:
            ray_tpu.shutdown()
            c.stop()
