"""North-star benchmark: steady-state placement rounds, 1M tasks x 1k nodes.

BASELINE.json metric: "scheduler throughput (tasks/sec) + p50 placement
latency @1M tasks/1k nodes"; north star: schedule 1M pending tasks across a
1k-node simulated cluster in <50 ms p50 on one TPU, matching the CPU
HybridPolicy bit-for-bit.  vs_baseline = 50ms / measured_p50 (>1 beats it).

What is timed, per heartbeat round (the pipeline a raylet heartbeat runs):
  1. device water-fill over the scheduling-class batch (ray_tpu.ops),
  2. device->host transfer of the (classes x nodes) placement counts,
  3. host expansion of counts into per-node assignments for every task in
     each class queue (np.repeat per class — the runtime dispatches straight
     from per-class queues, matching the reference ClusterTaskManager's
     SchedulingClass-keyed queue).
Rounds run software-pipelined (dispatch all, then one batched fetch), which
is how a continuously-beating scheduler overlaps transfer with compute; the
fetch stacks all rounds on device and packs counts to int16 (provably safe:
a count is bounded by its class's queue depth < 2^15), halving bytes on the
host link — transfer is the dominant term, so this matters.  p50 is over
per-round wall time at steady state.  Scheduling-class *grouping* is
not timed: classes are interned at task submission (TaskSpec
.scheduling_class), identical to the reference.

Output: one JSON line.  It embeds the ``delta`` section: per-phase
breakdown (densify, host->HBM upload, dirty-row rescore, fused
water-fill+argmin, counts readback) and the delta-beat hit rate over
a churn workload driven through the real ClusterResourceManager dirty
journal (scheduling/cluster_resources.py delta_view ->
scheduling/policy.py DeltaScheduler).  The record names the device it
ran on; without a TPU the bench exits non-zero and prints no record.

r17 adds the ``budget_beat`` stage: per-(class, node) lease budgets
ride the beat's single packed readback, the timed loop includes the
board publish that feeds the lease grantor, and the record carries
the device-vs-CPU-oracle budget parity gate plus
``readbacks_per_beat: 1``.
"""

import json
import time

import numpy as np

N_NODES = 1000
N_RES = 8
N_CLASSES = 64
N_TASKS = 1_000_000
ROUNDS = 20         # rounds per timed repetition
REPS = 9            # p50 over per-round means of these repetitions
TARGET_MS = 50.0


def build_problem(seed=0):
    rng = np.random.default_rng(seed)
    totals = rng.integers(400, 12800, size=(N_NODES, N_RES)).astype(np.int32)
    totals[rng.random(totals.shape) < 0.25] = 0
    used = (totals * rng.random(totals.shape) * 0.5).astype(np.int32)
    avail = totals - used
    node_mask = np.ones(N_NODES, dtype=bool)

    reqs = rng.integers(0, 400, size=(N_CLASSES, N_RES)).astype(np.int32)
    reqs[rng.random(reqs.shape) < 0.5] = 0
    counts = rng.multinomial(N_TASKS, np.full(N_CLASSES, 1 / N_CLASSES))
    return totals, avail, node_mask, reqs, counts.astype(np.int32)


def expand(counts_host, n_nodes):
    """Per-queue-position node assignment for every scheduling class.

    counts_host: (G, N+1).  Returns list of per-class int32 arrays (node row
    per task, -1 infeasible) — the order tasks are popped from each class
    queue.
    """
    cols = np.concatenate([np.arange(n_nodes, dtype=np.int32),
                           np.array([-1], dtype=np.int32)])
    return [np.repeat(cols, counts_host[g])
            for g in range(counts_host.shape[0])]


def measure_plane_throughput(mb: int = 32) -> float:
    """Object-plane transfer throughput (MB/s): one chunked
    arena-to-arena pull between two in-process stores over a real
    loopback RPC server — the wire path agents use
    (runtime/object_plane.py)."""
    import os
    import tempfile

    from ray_tpu.common.ids import ObjectID
    from ray_tpu.native import Arena
    from ray_tpu.rpc import RpcServer
    from ray_tpu.runtime.object_plane import ObjectPlane
    from ray_tpu.runtime.object_store import MemoryStore

    size = mb << 20
    tmp = tempfile.mkdtemp(prefix="bench_plane_")
    src_arena = Arena(os.path.join(tmp, "src"), size * 2, create=True)
    dst_arena = Arena(os.path.join(tmp, "dst"), size * 2, create=True)
    src = MemoryStore(arena=src_arena,
                      spill_dir=os.path.join(tmp, "s_spill"))
    dst = MemoryStore(arena=dst_arena,
                      spill_dir=os.path.join(tmp, "d_spill"))
    src_plane, dst_plane = ObjectPlane(src), ObjectPlane(dst)
    server = RpcServer(src_plane.handlers()).start()
    oid = ObjectID(os.urandom(28))
    src.put_serialized(oid, os.urandom(size))
    try:
        t0 = time.perf_counter()
        ok = dst_plane.pull_into_local(oid, size, server.address)
        dt = time.perf_counter() - t0
        assert ok, "plane transfer failed"
        return round(mb / dt, 1)
    finally:
        server.stop()
        src_plane.shutdown()
        dst_plane.shutdown()
        src_arena.close()
        dst_arena.close()
        import shutil
        shutil.rmtree(tmp, ignore_errors=True)


def delta_churn_bench(n_nodes: int = 256, n_classes: int = 32,
                      beats: int = 30, churn: int = 12,
                      seed: int = 0, shards: int = 1) -> dict:
    """Delta-scheduling heartbeat under node churn, on the REAL stack:
    a ClusterResourceManager takes random subtract/add_back mutations
    between beats and the DeltaScheduler syncs its HBM mirror from the
    dirty journal.  Returns hit rate, per-beat p50, the per-phase
    breakdown (profile mode inserts device syncs, so phase sums exceed
    the unprofiled beat wall time), and bit-parity of the final beat
    vs the CPU oracle.

    ``shards > 1`` runs the mesh-sharded engine instead (r14): node
    rows partitioned over the device mesh with the two-level ICI/DCN
    argmin reduce — same workload, same parity gate."""
    from ray_tpu.common.ids import NodeID
    from ray_tpu.common.resources import NodeResources, ResourceRequest
    from ray_tpu.scheduling import (ClusterResourceManager, DeltaScheduler,
                                    ShardedDeltaScheduler,
                                    schedule_grouped_oracle)

    rng = np.random.default_rng(seed)
    crm = ClusterResourceManager(capacity=n_nodes)
    for _ in range(n_nodes):
        crm.add_node(NodeID.from_random(), NodeResources(
            {"CPU": int(rng.integers(4, 64)),
             "memory": int(rng.integers(8, 256)),
             "TPU": int(rng.integers(0, 8))}))
    class_reqs = [ResourceRequest({"CPU": int(rng.integers(1, 4)),
                                   "memory": float(rng.integers(0, 8))})
                  for _ in range(n_classes)]
    t0 = time.perf_counter()
    vecs = np.stack([crm.intern_request(r) for r in class_reqs])
    densify_ms = (time.perf_counter() - t0) * 1e3
    counts = rng.integers(1, 40, size=n_classes).astype(np.int32)

    eng = ShardedDeltaScheduler(crm, shards) if shards > 1 \
        else DeltaScheduler(crm)
    eng.profile = True
    eng.phase_ms["densify"] += densify_ms
    churn_req = ResourceRequest({"CPU": 1})
    debts: list[int] = []
    got = eng.beat(vecs, counts)            # beat 1: the full sync
    per_beat = []
    for _ in range(beats):
        for _ in range(churn):
            if debts and rng.random() < 0.5:
                crm.add_back(debts.pop(), churn_req)
            else:
                row = int(rng.integers(0, n_nodes))
                crm.force_subtract(row, churn_req)
                debts.append(row)
        t0 = time.perf_counter()
        got = eng.beat(vecs, counts)
        per_beat.append((time.perf_counter() - t0) * 1e3)
    want = schedule_grouped_oracle(crm.snapshot(), vecs, counts)
    n_beats = eng.stats["beats"]
    return {
        "workload": f"{n_nodes} nodes x {n_classes} classes, "
                    f"{churn} dirty rows/beat x {beats} beats",
        "hit_rate": round(eng.hit_rate(), 4),
        "beat_p50_ms": round(float(np.percentile(per_beat, 50)), 3),
        "phases_ms_per_beat": {k: round(v / n_beats, 4)
                               for k, v in eng.phase_ms.items()},
        "oracle_parity": bool((got == want).all()),
        "shards": eng.stats.get("shards", 1),
        **{k: eng.stats[k] for k in ("beats", "delta_beats",
                                     "full_rescores", "clean_beats",
                                     "rows_uploaded")},
    }


# sharded-phase names for the r14 breakdown (ISSUE 14 satellite 1):
# the engine's phase timers keep the r08 keys; the record maps them to
# what each phase IS on the sharded path.
_SHARDED_PHASE_NAMES = {"h2d": "shard_upload", "score": "local_score",
                        "argmin": "cross_device_reduce",
                        "readback": "readback", "densify": "densify"}

# per-device HBM for the ceiling model, keyed by ``device_kind``.
# Source: Google Cloud documentation, "TPU v5e" (16 GB HBM2 per chip).
_HBM_BYTES = {"TPU v5 lite": 16 * 10**9}


def hbm_bytes(device_kind: str) -> int:
    """Published HBM of one device; an unknown kind is an error."""
    try:
        return _HBM_BYTES[device_kind]
    except KeyError:
        raise KeyError(f"no published HBM size for device kind "
                       f"{device_kind!r}; add it to bench._HBM_BYTES "
                       "with its source") from None


def _hbm_ceiling_classes(n_nodes: int, n_res: int, shards: int,
                         budget: int) -> int:
    """Largest resident class count whose scheduling plane fits ONE
    device's HBM at S-way sharding, at ``n_nodes`` nodes (the contract
    caps nodes at MAX_NODES, so classes are the unbounded axis of the
    (tasks x nodes) problem).  Per device: its N/S key columns cost
    4*N/S bytes per class plus the replicated (C, R) request row; the
    node-state rows (totals/avail/masks) are class-independent.  Key
    columns dominate, so max C scales ~linearly with S."""
    rows = -(-n_nodes // shards)                # N/S, ceil
    per_class = 4 * rows + 4 * n_res
    fixed = rows * (8 * n_res + 2)
    return max((budget - fixed) // per_class, 0)


def sharded_delta_bench(n_nodes: int = 512, n_classes: int = 48,
                        beats: int = 25, churn: int = 24,
                        seed: int = 0, shards: int = 0) -> dict:
    """The r14 sharded-vs-fused stage: the SAME churn workload through
    the single-device engine and the mesh-sharded engine, with the
    sharded per-phase breakdown (shard upload / local score /
    cross-device reduce / readback) and the HBM-ceiling model showing
    how much larger a problem the mesh holds than one chip."""
    import jax

    from ray_tpu.ops.shard_reduce import resolve_shards
    s = resolve_shards(shards, len(jax.local_devices()))
    fused = delta_churn_bench(n_nodes, n_classes, beats, churn, seed,
                              shards=1)
    rec: dict = {"shards": s, "fused": fused}
    if s > 1:
        sharded = delta_churn_bench(n_nodes, n_classes, beats, churn,
                                    seed, shards=s)
        sharded["phases_ms_per_beat"] = {
            _SHARDED_PHASE_NAMES.get(k, k): v
            for k, v in sharded["phases_ms_per_beat"].items()}
        rec["sharded"] = sharded
        rec["bit_exact_fused_vs_sharded"] = bool(
            sharded["oracle_parity"] and fused["oracle_parity"])
    else:
        rec["sharded"] = None
        rec["note"] = "one device: nothing to shard"
    # ONE counts fetch per beat by construction, at any shard count:
    # fused_beat gathers counts+argmin device-side and the host reads
    # one (G, N+1) buffer (scheduling/policy.py beat()).
    rec["readbacks_per_beat"] = 1
    # HBM ceiling model at the contract's full node axis (MAX_NODES,
    # 8 resource columns): how many resident scheduling classes — the
    # unbounded axis of the (tasks x nodes) problem — the aggregate
    # mesh holds vs one chip.
    from ray_tpu.scheduling import MAX_NODES
    kn, kr = MAX_NODES, 8
    budget = hbm_bytes(jax.devices()[0].device_kind)
    single = _hbm_ceiling_classes(kn, kr, 1, budget)
    sharded_c = _hbm_ceiling_classes(kn, kr, max(s, 1), budget)
    rec["hbm_ceiling_model"] = {
        "nodes": kn, "resources": kr,
        "hbm_bytes_per_device": budget,
        "max_classes_single_device": single,
        "max_classes_sharded": sharded_c,
        "problem_ratio": round(sharded_c / max(single, 1), 2),
    }
    return rec


def budget_beat_bench(n_nodes: int = 256, n_classes: int = 24,
                      beats: int = 20, churn: int = 16,
                      seed: int = 0, shards: int = 0) -> dict:
    """The r17 tentpole stage: the fused beat emits per-(class, node)
    lease budgets INSIDE its single packed readback, and the timed
    region covers the full loop a raylet heartbeat runs — churned
    beat, packed counts+budgets fetch, and the board publish that
    re-keys budget rows for the lease grantor.  Parity gate: the
    final beat's budget rows must be bit-identical to the CPU oracle
    twin (``contract.compute_budgets`` on the post-water-fill state).
    Runs fused always; when the backend has >1 device the same
    workload repeats on the mesh-sharded engine with the same gate."""
    import jax

    from ray_tpu.common.ids import NodeID
    from ray_tpu.common.resources import NodeResources, ResourceRequest
    from ray_tpu.leasing.board import BudgetBoard
    from ray_tpu.ops.shard_reduce import resolve_shards
    from ray_tpu.scheduling import (ClusterResourceManager, DeltaScheduler,
                                    ShardedDeltaScheduler,
                                    schedule_grouped_oracle)
    from ray_tpu.scheduling.contract import compute_budgets

    def one_engine(n_shards: int) -> dict:
        rng = np.random.default_rng(seed)
        crm = ClusterResourceManager(capacity=n_nodes)
        for _ in range(n_nodes):
            crm.add_node(NodeID.from_random(), NodeResources(
                {"CPU": int(rng.integers(4, 64)),
                 "memory": int(rng.integers(8, 256))}))
        class_reqs = [ResourceRequest(
            {"CPU": int(rng.integers(1, 4)),
             "memory": float(rng.integers(0, 8))})
            for _ in range(n_classes)]
        vecs = np.stack([crm.intern_request(r) for r in class_reqs])
        counts = rng.integers(1, 40, size=n_classes).astype(np.int32)
        eng = ShardedDeltaScheduler(crm, n_shards) if n_shards > 1 \
            else DeltaScheduler(crm)
        board = BudgetBoard()
        churn_req = ResourceRequest({"CPU": 1})
        debts: list[int] = []
        eng.beat(vecs, counts)              # beat 1: the full sync
        per_beat = []
        for _ in range(beats):
            for _ in range(churn):
                if debts and rng.random() < 0.5:
                    crm.add_back(debts.pop(), churn_req)
                else:
                    row = int(rng.integers(0, n_nodes))
                    crm.force_subtract(row, churn_req)
                    debts.append(row)
            t0 = time.perf_counter()
            eng.beat(vecs, counts)
            budgets = eng.last_budgets()
            board.publish(eng.budget_seq,
                          {str(i): budgets[i] for i in range(n_classes)})
            per_beat.append((time.perf_counter() - t0) * 1e3)
        st = crm.snapshot()
        schedule_grouped_oracle(st, vecs, counts)
        want = compute_budgets(st.totals, st.avail, vecs,
                               node_mask=st.node_mask)
        parity = all(
            np.array_equal(eng.budget_row_host(v), want[i])
            for i, v in enumerate(vecs))
        return {
            "workload": f"{n_nodes} nodes x {n_classes} classes, "
                        f"{churn} dirty rows/beat x {beats} beats",
            "beat_plus_publish_p50_ms":
                round(float(np.percentile(per_beat, 50)), 3),
            "budget_parity": parity,
            "budget_rows_per_beat": n_classes,
            "nonzero_budget_fraction":
                round(float((want[:, st.node_mask] > 0).mean()), 4),
            "board": board.stats(),
            "shards": eng.stats.get("shards", 1),
        }

    s = resolve_shards(shards, len(jax.local_devices()))
    rec: dict = {"fused": one_engine(1),
                 "sharded": one_engine(s) if s > 1 else None,
                 # budgets ride the beat's ONE sanctioned fetch: the
                 # packed (G + C, N+1) buffer (scheduling/policy.py)
                 "readbacks_per_beat": 1}
    rec["budget_parity"] = rec["fused"]["budget_parity"] and (
        rec["sharded"] is None or rec["sharded"]["budget_parity"])
    return rec


def dispatch_lease_bench(num_nodes: int = 10000, jobs: int = 1000,
                         tasks_per_job: int = 16, seed: int = 0,
                         kill_head_at: float | None = 60.0) -> dict:
    """The r15 tentpole surface: lease-plane dispatch throughput vs the
    head-only path on the identical seeded job stream, plus the
    hot-standby failover window (head SIGKILL mid-stream).  Pure
    simulation over modeled head service time (sim/dispatch_bench.py)
    — deterministic, replay-stable, no device needed."""
    from ray_tpu.sim.dispatch_bench import run_dispatch_comparison
    cmp_ = run_dispatch_comparison(num_nodes, jobs, tasks_per_job,
                                   seed=seed, kill_head_at=kill_head_at)
    rec = {
        "nodes": num_nodes, "jobs": jobs,
        "tasks": jobs * tasks_per_job, "seed": seed,
        "speedup_vs_head_only": cmp_["speedup"],
        "head_only_throughput_per_s":
            cmp_["head_only"]["dispatch_throughput_per_s"],
        "lease_throughput_per_s":
            cmp_["lease"]["dispatch_throughput_per_s"],
        "lease_hit_rate": cmp_["lease"]["lease_hit_rate"],
        "spillbacks": cmp_["lease"]["spillbacks"],
        "trace_hash_head_only": cmp_["head_only"]["trace_hash"],
        "trace_hash_lease": cmp_["lease"]["trace_hash"],
    }
    fo = cmp_.get("failover")
    if fo is not None:
        rec["failover"] = {
            "kill_head_at_s": kill_head_at,
            "promotions": fo["promotions"],
            "failover_ms": fo["failover_ms"],
            "jobs_completed": fo["jobs_completed"],
            "lease_hit_rate": fo["lease_hit_rate"],
            "lease_revocations": fo["lease_revocations"],
            "trace_hash": fo["trace_hash"],
        }
    return rec


def main():
    from ray_tpu.util.compile_cache import enable_compile_cache
    enable_compile_cache()
    import jax
    import jax.numpy as jnp

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"bench: needs a TPU; JAX found {dev.platform} "
                         f"({dev.device_kind})")
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}

    from ray_tpu.ops import schedule_grouped
    from ray_tpu.scheduling import threshold_fp

    totals, avail, node_mask, reqs, counts = build_problem()
    thr = threshold_fp(0.5)
    # int16 packing safety: a per-node count never exceeds its class's
    # queue depth
    assert counts.max() < 2 ** 15, counts.max()

    d = jnp.asarray
    args = (d(totals), d(avail), d(node_mask), d(reqs), d(counts),
            jnp.ones((N_CLASSES, N_NODES), dtype=bool), jnp.int32(thr))

    @jax.jit
    def pack_rounds(outs):
        return jnp.stack(outs).astype(jnp.int16)

    # warmup/compile (np.asarray is the reliable sync on every backend)
    np.asarray(pack_rounds([schedule_grouped(*args)[0]
                            for _ in range(ROUNDS)]))

    per_round = []
    for _ in range(REPS):
        t0 = time.perf_counter()
        outs = [schedule_grouped(*args)[0] for _ in range(ROUNDS)]
        hosts = np.asarray(pack_rounds(outs))   # one (R, G, N+1) fetch
        assignments = [expand(h, N_NODES) for h in hosts]
        dt = (time.perf_counter() - t0) * 1e3 / ROUNDS
        per_round.append(dt)
    p50 = float(np.percentile(per_round, 50))

    # compute-only: device rounds synced WITHOUT the counts fetch or the
    # host expansion — isolates kernel time from the transfer+host terms
    compute_rounds = []
    for _ in range(REPS):
        t0 = time.perf_counter()
        outs = [schedule_grouped(*args)[0] for _ in range(ROUNDS)]
        jax.block_until_ready(outs[-1])
        compute_rounds.append(
            (time.perf_counter() - t0) * 1e3 / ROUNDS)
    compute_ms = float(np.percentile(compute_rounds, 50))

    total = int(hosts[-1].astype(np.int64).sum())
    assert total == N_TASKS, (total, N_TASKS)
    placed = int(hosts[-1][:, :-1].astype(np.int64).sum())  # excl. the
    #                                                 infeasible column
    assert placed > N_TASKS // 2, f"only {placed}/{N_TASKS} placeable"
    assert sum(a.shape[0] for a in assignments[-1]) == N_TASKS

    # bit-for-bit parity vs the CPU oracle over the FULL 64-class batch
    # (~3 s on host; the fixed-point short-cut in schedule_grouped_oracle
    # keeps the O(G·N·R) loop cheap)
    from ray_tpu.scheduling import ClusterState, schedule_grouped_oracle
    st = ClusterState(totals.copy(), avail.copy(), node_mask.copy())
    want = schedule_grouped_oracle(st, reqs, counts, spread_threshold=0.5)
    parity = bool((hosts[-1].astype(np.int32) == want).all())

    print(json.dumps({
        "metric": "p50 heartbeat time: 1M tasks x 1k nodes, bit-exact hybrid"
                  + ("" if parity else " [PARITY FAIL]"),
        "value": round(p50, 3),
        "unit": "ms",
        "vs_baseline": round(TARGET_MS / p50, 2),
        "device": device,
        # control: compute_only_ms excludes the counts fetch + host
        # expansion
        "compute_only_ms": round(compute_ms, 3),
        "plane_transfer_mbps": measure_plane_throughput(),
        # the r08 tentpole surface: device-resident delta heartbeat
        # under churn — phase breakdown + hit rate (module docstring)
        "delta": delta_churn_bench(n_nodes=N_NODES, n_classes=N_CLASSES,
                                   beats=30, churn=32),
        # the r14 tentpole surface: sharded-vs-fused beat + the
        # two-level reduce phase breakdown + the HBM-ceiling model
        "sharded": sharded_delta_bench(n_nodes=N_NODES,
                                       n_classes=N_CLASSES,
                                       beats=20, churn=32),
        # the r15 tentpole surface: lease-plane dispatch + failover
        # (pure sim — the same numbers with or without the device)
        "dispatch": dispatch_lease_bench(num_nodes=10000, jobs=1000,
                                         tasks_per_job=16,
                                         kill_head_at=60.0),
        # the r17 tentpole surface: budgets riding the beat's single
        # packed readback + board publish, with the oracle parity gate
        "budget_beat": budget_beat_bench(n_nodes=N_NODES,
                                         n_classes=N_CLASSES,
                                         beats=20, churn=32),
    }))


if __name__ == "__main__":
    main()
