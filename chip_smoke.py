"""Chip smoke: the raylet's placement beat, once, on a local TPU.

    python chip_smoke.py               # one chip: phases (a), (b), (c)
    python chip_smoke.py --four-chips  # sharded beat vs one-device engine

(a) device: JAX must report a TPU; there is no CPU fallback.
(b) heartbeat at the north-star size through the raylet's own engine
    (``make_delta_scheduler``, the call ``Raylet._schedule_rows_delta``
    makes): 1,000 nodes x 8 resource columns, 64 interned classes, a
    1,000,000-task backlog; one full-sync beat, then churn beats of 32
    dirty rows.  Every beat places the whole backlog, three beats are
    bit-exact with the CPU oracle, the last beat's lease budgets equal
    ``contract.compute_budgets``, and ``schedule_grouped`` on
    ``bench.build_problem()`` matches the oracle.
(c) live runtime: ``ray_tpu.init`` plus a few raylets with the device
    batch floor lowered, ~8k tasks and an actor call, every result
    fetched under a timeout.

``--four-chips`` runs only the sharded beat (``flat`` and ``two_level``
over 4 chips) against the single-device engine and the oracle.

Each phase prints one JSON line; times in it are information, not a
benchmark.  The last line is ``{"ok": true, "device": {...}}``.  Any
failed check raises, and the process exits non-zero.
"""

from __future__ import annotations

import json
import sys
import time

import numpy as np

N_NODES = 1000
N_RES = 8
N_CLASSES = 64
N_TASKS = 1_000_000
CHURN_BEATS = 10
CHURN_ROWS = 32
LIVE_TASKS = 8000
SEED = 0

_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"
_CACHE_MISS = "/jax/compilation_cache/cache_misses"


class CompileLog:
    """Compile seconds and persistent-cache hits, from JAX's own
    monitoring events (a cache hit is counted inside the compile
    event, so warm runs show fewer seconds)."""

    def __init__(self):
        self.secs = 0.0
        self.hits = 0
        self.misses = 0

    def install(self) -> "CompileLog":
        from jax import monitoring
        monitoring.register_event_duration_secs_listener(self._on_duration)
        monitoring.register_event_listener(self._on_event)
        return self

    def _on_duration(self, event, duration_secs, **_kw):
        if event == _BACKEND_COMPILE:
            self.secs += duration_secs

    def _on_event(self, event, **_kw):
        if event == _CACHE_HIT:
            self.hits += 1
        elif event == _CACHE_MISS:
            self.misses += 1

    def since(self, mark: tuple) -> dict:
        return {"compile_s": self.secs - mark[0],
                "cache_hits": self.hits - mark[1],
                "cache_misses": self.misses - mark[2]}

    def mark(self) -> tuple:
        return (self.secs, self.hits, self.misses)


def check(ok, what) -> None:
    """A failed check raises; ``assert`` would vanish under ``-O``."""
    if not ok:
        raise RuntimeError(f"chip_smoke check failed: {what}")


def device_check(platform: str = "tpu", min_count: int = 1) -> dict:
    """Phase (a): the platform JAX found, or exit non-zero."""
    import jax
    devs = jax.devices()
    d0 = devs[0]
    if d0.platform != platform:
        raise SystemExit(f"chip_smoke: needs a {platform} device; JAX "
                         f"found {d0.platform} ({d0.device_kind})")
    if len(devs) < min_count:
        raise SystemExit(f"chip_smoke: needs {min_count} {platform} "
                         f"devices; JAX found {len(devs)}")
    return {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(devs)}


def build_backlog(n_nodes: int = N_NODES, n_classes: int = N_CLASSES,
                  n_tasks: int = N_TASKS, seed: int = SEED):
    """A CRM of ``n_nodes`` partly used nodes over 8 resource columns,
    ``n_classes`` distinct interned request vectors and a multinomial
    backlog of ``n_tasks`` over them.  Quantities stay far under the
    int32 cap of 1310.72 units per node."""
    from ray_tpu.common.ids import NodeID
    from ray_tpu.common.resources import (PREDEFINED_RESOURCES,
                                          NodeResources, ResourceRequest)
    from ray_tpu.scheduling import ClusterResourceManager

    names = list(PREDEFINED_RESOURCES)
    names += [f"custom_{i}" for i in range(N_RES - len(names))]
    rng = np.random.default_rng(seed)
    crm = ClusterResourceManager(num_resource_slots=N_RES,
                                 capacity=n_nodes)
    totals = rng.integers(400, 12800, size=(n_nodes, N_RES))
    totals[rng.random(totals.shape) < 0.25] = 0
    used = (totals * rng.random(totals.shape) * 0.5).astype(np.int64)
    for t_row, u_row in zip(totals, used):
        nr = NodeResources({n: int(c) / 100 for n, c in zip(names, t_row)})
        nr.available_cu = {n: int(t - u) for n, t, u
                           in zip(names, t_row, u_row) if t}
        crm.add_node(NodeID.from_random(), nr)
    reqs: dict[tuple, ResourceRequest] = {}
    while len(reqs) < n_classes:
        cu = rng.integers(0, 400, size=N_RES)
        cu[rng.random(N_RES) < 0.5] = 0
        reqs.setdefault(tuple(cu), ResourceRequest(
            {n: int(c) / 100 for n, c in zip(names, cu) if c}))
    vecs = np.stack([crm.intern_request(r) for r in reqs.values()])
    check(vecs.shape == (n_classes, N_RES), vecs.shape)
    counts = rng.multinomial(
        n_tasks, np.full(n_classes, 1 / n_classes)).astype(np.int32)
    return crm, vecs, counts


class Churn:
    """Between beats: ``rows`` CPU debits or refunds on random nodes,
    through the CRM's own force_subtract/add_back (its dirty journal)."""

    def __init__(self, crm, rows: int, seed: int):
        from ray_tpu.common.resources import ResourceRequest
        self.crm, self.rows = crm, rows
        self.rng = np.random.default_rng(seed)
        self.req = ResourceRequest({"CPU": 1})
        self.debts: list[int] = []

    def step(self) -> None:
        n = self.crm.num_nodes()
        for _ in range(self.rows):
            if self.debts and self.rng.random() < 0.5:
                self.crm.add_back(self.debts.pop(), self.req)
            else:
                row = int(self.rng.integers(0, n))
                self.crm.force_subtract(row, self.req)
                self.debts.append(row)


def _oracle(crm, vecs, counts):
    """(counts, post-fill state) of the CPU oracle on a fresh snapshot."""
    from ray_tpu.scheduling import schedule_grouped_oracle
    st = crm.snapshot()
    return schedule_grouped_oracle(st, vecs, counts), st


def _budget_parity(eng, vecs, st) -> bool:
    from ray_tpu.scheduling.contract import compute_budgets
    want = compute_budgets(st.totals, st.avail, vecs,
                           node_mask=st.node_mask)
    return all(np.array_equal(eng.budget_row_host(v), want[i])
               for i, v in enumerate(vecs))


def _platforms(arr) -> list[str]:
    return sorted({d.platform for d in arr.devices()})


def heartbeat_phase(platform: str = "tpu", n_nodes: int = N_NODES,
                    n_classes: int = N_CLASSES, n_tasks: int = N_TASKS,
                    beats: int = CHURN_BEATS, churn: int = CHURN_ROWS,
                    seed: int = SEED) -> dict:
    """Phase (b): the raylet's delta engine at the north-star size."""
    from ray_tpu.scheduling import DeltaScheduler, make_delta_scheduler

    crm, vecs, counts = build_backlog(n_nodes, n_classes, n_tasks, seed)
    eng = make_delta_scheduler(crm)
    check(type(eng) is DeltaScheduler, type(eng))
    churner = Churn(crm, churn, seed + 1)
    checks = {0, beats // 2, beats}
    beat_ms, parity = [], {}
    for b in range(beats + 1):
        if b:
            churner.step()
        t0 = time.perf_counter()
        got = eng.beat(vecs, counts)        # host array: the beat synced
        beat_ms.append((time.perf_counter() - t0) * 1e3)
        check(int(got.sum()) == n_tasks, (b, int(got.sum())))
        if b == 0:
            on = _platforms(eng._totals)
            check(on == [platform], f"residents on {on}, not {platform}")
        if b in checks:
            want, st = _oracle(crm, vecs, counts)
            parity[f"beat{b}"] = bool(np.array_equal(got, want))
            check(parity[f"beat{b}"], f"beat {b} diverged from oracle")
    budget_ok = _budget_parity(eng, vecs, st)       # st of the last beat
    check(budget_ok, "last beat's lease budgets diverged from the oracle")
    return {"phase": "heartbeat", "engine": type(eng).__name__,
            "residents_on": platform, "nodes": n_nodes,
            "resources": N_RES, "classes": n_classes, "tasks": n_tasks,
            "churn_rows_per_beat": churn, "parity": parity,
            "budget_parity": budget_ok,
            "full_sync_beat_ms": beat_ms[0], "churn_beat_ms": beat_ms[1:],
            "stats": dict(eng.stats),
            "note": "beat times are information, not a benchmark"}


def continuity_phase(problem) -> dict:
    """``schedule_grouped`` on the r01-r03 problem, against the oracle."""
    import jax.numpy as jnp

    from ray_tpu.ops import schedule_grouped
    from ray_tpu.scheduling import ClusterState, schedule_grouped_oracle
    from ray_tpu.scheduling.contract import threshold_fp

    totals, avail, mask, reqs, counts = problem
    g, n = reqs.shape[0], totals.shape[0]
    t0 = time.perf_counter()
    out, _ = schedule_grouped(
        jnp.asarray(totals), jnp.asarray(avail), jnp.asarray(mask),
        jnp.asarray(reqs), jnp.asarray(counts), jnp.ones((g, n), bool),
        jnp.int32(threshold_fp(0.5)))
    got = np.asarray(out)
    first_call_ms = (time.perf_counter() - t0) * 1e3
    want = schedule_grouped_oracle(
        ClusterState(totals.copy(), avail.copy(), mask.copy()), reqs,
        counts, spread_threshold=0.5)
    ok = bool(np.array_equal(got, want))
    check(ok, "schedule_grouped diverged from the oracle")
    check(int(got.sum()) == int(counts.sum()), "tasks lost or invented")
    return {"parity": ok, "first_call_ms": first_call_ms,
            "shape": [n, totals.shape[1], g], "tasks": int(counts.sum())}


def live_phase(platform: str = "tpu", n_tasks: int = LIVE_TASKS,
               extra_nodes: int = 3, wave: int = 1000,
               timeout_s: float = 600.0) -> dict:
    """Phase (c): the live runtime's rounds reach the device beat."""
    import ray_tpu
    from ray_tpu.api import _get_runtime

    # batch floor 1: every all-DEFAULT round takes the device beat
    ray_tpu.init(resources={"CPU": 8, "memory": 8}, num_workers=2,
                 system_config={"scheduler_device_batch_min": 1})
    try:
        cluster = _get_runtime().cluster
        for _ in range(extra_nodes):
            cluster.add_node(resources={"CPU": 8, "memory": 8},
                             num_workers=2)

        @ray_tpu.remote
        def inc(x):
            return x + 1

        @ray_tpu.remote
        class Counter:
            def __init__(self):
                self.n = 0

            def add(self, k):
                self.n += k
                return self.n

        # waves, so that several rounds follow the one that compiles
        t0 = time.perf_counter()
        for lo in range(0, n_tasks, wave):
            want = list(range(lo + 1, min(lo + wave, n_tasks) + 1))
            refs = [inc.remote(x - 1) for x in want]
            got = ray_tpu.get(refs, timeout=timeout_s)
            check(got == want, f"wrong results in the wave from {lo}")
        wall_s = time.perf_counter() - t0
        actor = Counter.remote()
        check(ray_tpu.get(actor.add.remote(5), timeout=timeout_s) == 5,
              "wrong actor result")
        raylets = list(cluster.raylets.values())
        engines = [r._delta_engine for r in raylets
                   if r._delta_engine is not None]
        check(engines, "no raylet built a device engine")
        on = sorted({p for e in engines for p in _platforms(e._totals)})
        check(on == [platform], f"engine residents on {on}")
        rounds = [d for r in raylets for d in r._round_durations]
        return {"phase": "live", "tasks": n_tasks, "results_ok": True,
                "actor_ok": True, "raylets": len(raylets),
                "device_engines": len(engines),
                "device_beats": sum(e.stats["beats"] for e in engines),
                "round_ms": [d * 1e3 for d in rounds],
                "tasks_wall_s": wall_s,
                "note": "times are information, not a benchmark"}
    finally:
        ray_tpu.shutdown()


def four_chip_phase(platform: str = "tpu", n_shards: int = 4,
                    n_nodes: int = N_NODES, n_classes: int = N_CLASSES,
                    n_tasks: int = N_TASKS, beats: int = CHURN_BEATS,
                    churn: int = CHURN_ROWS, seed: int = SEED) -> dict:
    """The sharded beat, ``flat`` and ``two_level``, bit-for-bit against
    the single-device engine every beat and the oracle at three."""
    from ray_tpu.scheduling import DeltaScheduler, ShardedDeltaScheduler

    crm, vecs, counts = build_backlog(n_nodes, n_classes, n_tasks, seed)
    single = DeltaScheduler(crm)
    sharded = {m: ShardedDeltaScheduler(crm, n_shards, m)
               for m in ("flat", "two_level")}
    churner = Churn(crm, churn, seed + 1)
    checks = {0, beats // 2, beats}
    beat_ms = {m: [] for m in ("single", *sharded)}
    oracle_ok = []
    for b in range(beats + 1):
        if b:
            churner.step()
        t0 = time.perf_counter()
        ref = single.beat(vecs, counts)
        beat_ms["single"].append((time.perf_counter() - t0) * 1e3)
        check(int(ref.sum()) == n_tasks, (b, int(ref.sum())))
        for m, eng in sharded.items():
            t0 = time.perf_counter()
            got = eng.beat(vecs, counts)
            beat_ms[m].append((time.perf_counter() - t0) * 1e3)
            check(np.array_equal(got, ref), f"{m} diverged at beat {b}")
        if b in checks:
            want, st = _oracle(crm, vecs, counts)
            check(np.array_equal(ref, want), f"oracle diverged at beat {b}")
            oracle_ok.append(b)
    modes = {}
    for m, eng in sharded.items():
        devs = eng._totals.sharding.device_set
        check(eng.stats["shards"] == n_shards, eng.stats)
        check(len(devs) == n_shards, devs)
        check({d.platform for d in devs} == {platform}, devs)
        check(_budget_parity(eng, vecs, st), f"{m} budgets diverged")
        modes[m] = {"shards": eng.stats["shards"],
                    "devices": sorted(d.id for d in devs),
                    "mesh": list(eng._plane.mesh.devices.shape),
                    "bit_exact_vs_single": True, "budget_parity": True,
                    "beat_ms": beat_ms[m]}
    check(_budget_parity(single, vecs, st), "single-device budgets")
    return {"phase": "four_chips", "nodes": n_nodes, "classes": n_classes,
            "tasks": n_tasks, "beats": beats + 1,
            "oracle_parity_beats": oracle_ok, "modes": modes,
            "single_beat_ms": beat_ms["single"],
            "note": "beat times are information, not a benchmark"}


def _emit(rec: dict) -> None:
    print(json.dumps(rec), flush=True)


def main(argv: list[str]) -> int:
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-chip sharded beat")
    args = ap.parse_args(argv)

    # the repo first: a directory holding only this file fails here
    from ray_tpu.util.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    import jax
    log = CompileLog().install()

    device = device_check("tpu", 4 if args.four_chips else 1)
    _emit({"phase": "device", **device, "jax": jax.__version__,
           "compile_cache_dir": cache_dir})
    if args.four_chips:
        mark = log.mark()
        _emit({**four_chip_phase("tpu", 4), **log.since(mark)})
    else:
        from bench import build_problem
        mark = log.mark()
        rec = heartbeat_phase("tpu")
        rec["continuity"] = continuity_phase(build_problem())
        _emit({**rec, **log.since(mark)})
        mark = log.mark()
        _emit({**live_phase("tpu"), **log.since(mark)})
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
