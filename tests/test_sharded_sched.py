"""Mesh-sharded scheduling plane (r14): shard resolution, the
two-level mesh, the ShardPlane cache, and the slow 8-device
MULTICHIP dry-run of the sharded heartbeat.

conftest pins 8 virtual CPU devices, so the 2/4/8-way sharded paths
all execute in tier-1; the dry-run is `slow`-marked and skips
below 2 devices (a single chip)."""

import numpy as np
import pytest


class TestShardResolution:
    def test_resolve_shards(self):
        from ray_tpu.ops.shard_reduce import resolve_shards
        assert resolve_shards(0, 8) == 8        # auto: all devices
        assert resolve_shards(1, 8) == 1
        assert resolve_shards(5, 8) == 4        # round down to pow2
        assert resolve_shards(16, 8) == 8       # clamp to devices
        assert resolve_shards(3, 1) == 1
        assert resolve_shards(0, 6) == 4        # pow2 floor of 6

    def test_build_mesh_shapes(self):
        import jax

        from ray_tpu.ops.shard_reduce import build_mesh
        ndev = len(jax.local_devices())
        if ndev < 8:
            pytest.skip("needs the 8-device tier-1 harness")
        assert build_mesh(8, "flat").devices.shape == (1, 8)
        assert build_mesh(8, "two_level").devices.shape == (2, 4)
        assert build_mesh(1, "two_level").devices.shape == (1, 1)
        # CPU virtual devices expose no slice_index: auto == flat
        assert build_mesh(4, "auto").devices.shape == (1, 4)
        for mode in ("flat", "two_level", "auto"):
            assert build_mesh(2, mode).axis_names == ("dcn", "ici")

    def test_plane_cache_is_per_topology(self):
        from ray_tpu.ops.shard_reduce import plane_for
        assert plane_for(4, "flat") is plane_for(4, "flat")
        assert plane_for(4, "flat") is not plane_for(4, "two_level")


@pytest.mark.slow
class TestMultichipDryRun:
    """The 8-device MULTICHIP dry-run of the full sharded heartbeat:
    two_level (2, 4) mesh, big churny workload, one readback per beat,
    bit-exact vs the CPU oracle throughout."""

    def test_two_level_sharded_heartbeat(self):
        import jax

        from ray_tpu.common.ids import NodeID
        from ray_tpu.common.resources import NodeResources, ResourceRequest
        from ray_tpu.scheduling import (ShardedDeltaScheduler,
                                        schedule_grouped_oracle)
        from ray_tpu.scheduling.cluster_resources import \
            ClusterResourceManager
        ndev = len(jax.local_devices())
        if ndev < 2:
            pytest.skip(f"needs >= 2 devices for a sharded mesh "
                        f"(have {ndev})")
        shards = min(ndev, 8)
        rng = np.random.default_rng(42)
        n_nodes, n_classes = 600, 48
        crm = ClusterResourceManager(capacity=n_nodes)
        ids = [crm.id_of(crm.add_node(NodeID.from_random(), NodeResources(
            {"CPU": int(rng.integers(4, 64)),
             "memory": int(rng.integers(8, 256)),
             "TPU": int(rng.integers(0, 8))})))
            for _ in range(n_nodes)]
        class_reqs = [ResourceRequest(
            {"CPU": int(rng.integers(1, 4)),
             "memory": float(rng.integers(0, 8))})
            for _ in range(n_classes)]
        vecs = np.stack([crm.intern_request(cr) for cr in class_reqs])
        counts = rng.integers(1, 60, size=n_classes).astype(np.int32)
        eng = ShardedDeltaScheduler(crm, shards, reduce_mode="two_level")
        assert eng._plane.mesh.devices.shape == \
            (2, shards // 2) if shards >= 2 else (1, 1)
        one = ResourceRequest({"CPU": 1})
        debts = []
        for beat in range(20):
            for _ in range(24):
                if debts and rng.random() < 0.5:
                    crm.add_back(debts.pop(), one)
                else:
                    row = int(rng.integers(0, n_nodes))
                    crm.force_subtract(row, one)
                    debts.append(row)
            got = eng.beat(vecs, counts)
            want = schedule_grouped_oracle(crm.snapshot(), vecs, counts)
            np.testing.assert_array_equal(got, want)
        assert eng.stats["delta_beats"] >= 15
        assert eng.stats["shards"] == shards
