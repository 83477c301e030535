"""Runs one cell once: set-up, the measured window, the record to check.

``run_cell`` is the whole run short of printing.  ``run.py`` calls it
with ``platform="tpu"``; the CPU rehearsal and the tests call it with
``platform=None`` and smaller data files, and never through the command.
"""

from __future__ import annotations

import contextlib
import gc
import importlib
import json
import os
import shutil
import statistics
import tempfile
import time
from pathlib import Path

import numpy as np

from .generator import Churn, Deployment

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
# A traced window is short: the trace of one beat holds thousands of
# device events, and reading it counts against the run's 360 seconds.
TRACE_SECONDS = 2.0


class NoDevice(SystemExit):
    """JAX found no chip of the kind the cell asks for, or too few."""


def load_cell(workload: str, root: Path = ROOT) -> dict:
    """The cell, its configuration and traffic files, its configuration's
    round module and the metrics it reports, all found by name through
    ``BENCHMARK.json``."""
    spec = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise SystemExit(f"no workload {workload!r} in BENCHMARK.json")
    cell = cells[workload]
    cfg = {c["name"]: c for c in spec["configs"]}[cell["config"]]
    # a metric with a ``workloads`` list is reported in those cells,
    # one without it in every cell
    e2e, layer = ([m for m in spec[k]
                   if workload in m.get("workloads", [workload])]
                  for k in ("end_to_end", "per_layer"))
    config = json.loads((root / cfg["file"]).read_text())
    return {"cell": cell, "config": config,
            "traffic": json.loads(
                (BENCH / "traffic" / f"{cell['traffic']}.json").read_text()),
            "module": rounds_module(config),
            "end_to_end": e2e, "per_layer": layer}


def rounds_module(config: dict):
    """``rounds/<name>.py`` for the configuration's ``rounds`` key,
    ``hybrid`` where it has none: the round stream, the raylet call and
    its tally, and the reference's and the program's answers."""
    return importlib.import_module(
        f".rounds.{config.get('rounds', 'hybrid')}", __package__)


def counters(engine, crm, raylet) -> dict:
    """Every numeric entry of the program's counters, by source: the
    delta engine's ``stats``, the CRM's ``frozen_stats`` and the head
    raylet's ``queue_stats()``."""
    sources = {"engine": engine.stats if engine is not None else {},
               "crm": crm.frozen_stats, "raylet": raylet.queue_stats()}
    return {src: {k: v for k, v in d.items()
                  if isinstance(v, (int, float)) and not isinstance(v, bool)}
            for src, d in sources.items()}


def warmup_rounds(traffic: dict) -> int:
    """Rounds run before the window: every batch of the pool, and the
    first churn beats after the full sync."""
    return max(4, traffic.get("pool_batches", 0))


def sweep(ops: int) -> list[int]:
    """Churn sizes 1, 1, 2, 2, 4, 4, ... up to twice ``ops``."""
    return [1 << k for k in range((2 * ops).bit_length()) for _ in "ab"]


class Sampler:
    """Reservoir sample of ``k`` window rounds, drawn from the seed: the
    rounds whose answers are compared with the reference."""

    def __init__(self, k: int, seed: int):
        self.k = k
        self.rng = np.random.default_rng([seed, 2])
        self.kept: list[dict] = []

    def slot(self, i: int) -> int | None:
        if i < self.k:
            return i
        j = int(self.rng.integers(0, i + 1))
        return j if j < self.k else None

    def put(self, slot: int, rec: dict) -> None:
        if slot < len(self.kept):
            self.kept[slot] = rec
        else:
            self.kept.append(rec)


class BoardTap:
    """Keeps the rows the raylet last published on the budget board."""

    def __init__(self):
        from ray_tpu.leasing.board import budget_board
        self.board = budget_board()
        self.count = 0
        self.rows = None
        self._publish = self.board.publish
        self.board.publish = self._tap

    def _tap(self, seq, rows):
        self.count += 1
        self.rows = rows
        self._publish(seq, rows)

    def close(self):
        del self.board.publish


class BeatClock:
    """Time inside the engine's ``beat``, per round (traced runs only)."""

    def __init__(self, engine, span):
        self.engine, self.span = engine, span
        self.total = 0.0
        self.times: list[float] = []
        self._beat = engine.beat
        engine.beat = self._timed

    def _timed(self, *a, **kw):
        t0 = time.perf_counter()
        with self.span("bench.beat"):
            out = self._beat(*a, **kw)
        dt = time.perf_counter() - t0
        self.total += dt
        self.times.append(dt)
        return out

    def close(self):
        del self.engine.beat


def _peak_bytes(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks, default=0))


def run_cell(loaded: dict, seed: int, seconds: float, trace: bool,
             platform: str | None = "tpu",
             t_start: float | None = None) -> dict:
    """Set up the cell, drive the window, free the program; return what
    ``check.judge`` and ``run.py`` need."""
    t_start = time.perf_counter() if t_start is None else t_start
    import jax

    from ray_tpu.common.config import Config

    from .compile_log import compile_log

    log = compile_log()
    cell, config, traffic = loaded["cell"], loaded["config"], \
        loaded["traffic"]
    devices = jax.devices()
    if platform is not None and (devices[0].platform != platform
                                 or len(devices) < cell["chips"]):
        raise NoDevice(f"benchmark: {cell['name']} needs {cell['chips']} "
                       f"{platform} device(s); JAX found {len(devices)} "
                       f"{devices[0].platform} ({devices[0].device_kind})")
    used = devices[:cell["chips"]]
    setup_mark = log.mark()
    Config.reset({**config["system_config"], **traffic["system_config"]})
    dep = Deployment(config, seed)
    tap = BoardTap()
    clock = None
    trace_dir = None
    try:
        rounds = loaded["module"].Rounds(dep, config, traffic)
        churn = Churn(dep, traffic["dirty_request"],
                      traffic["dirty_ops_per_round"], seed)
        raylet = dep.raylet
        call, tally = rounds.call, rounds.tally

        warm = warmup_rounds(traffic)
        for i in range(warm):
            churn.step()
            call(rounds.at(i)[1])
        # the dirty-row upload is bucketed by size, per shard on a mesh:
        # rounds of 1, 2, 4, ... up to twice the churn's debits, each
        # twice, on as many distinct rows, warm every bucket a round can
        # meet
        for i, ops in enumerate(sweep(traffic["dirty_ops_per_round"]),
                                start=warm):
            churn.step(ops, refunds=False)
            call(rounds.at(i)[1])
        warm = i + 1
        engine = raylet._delta_engine
        span = contextlib.nullcontext
        if trace:
            span = jax.profiler.TraceAnnotation
            if engine is not None:
                clock = BeatClock(engine, span)
            trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
            jax.profiler.start_trace(
                trace_dir, profiler_options=_profile_options())
        seconds = min(seconds, TRACE_SECONDS) if trace else seconds
        counts0 = counters(engine, dep.crm, raylet)
        sampler = Sampler(traffic["sample_rounds"], seed)
        setup_compiles = log.since(setup_mark)
        gc.collect()
        gc.freeze()
        mark = log.mark()
        round_s, beat_s = [], []
        offered = placed = 0
        i = warm
        t0 = time.perf_counter()
        with span("bench.window"):
            while time.perf_counter() - t0 < seconds:
                with span("bench.churn"):
                    churn.step()
                j, payload = rounds.at(i)
                slot = sampler.slot(i - warm)
                if slot is not None:
                    avail = dep.avail.copy()     # the generator's record
                    published = tap.count
                in_beat = clock.total if clock else 0.0
                r0 = time.perf_counter()
                with span("bench.round"):
                    out = call(payload)
                r1 = time.perf_counter()
                round_s.append(r1 - r0)
                if clock:
                    beat_s.append(clock.total - in_beat)
                o, p = tally(payload, out)
                offered += o
                placed += p
                if slot is not None:
                    sampler.put(slot, {
                        "index": j, "avail": avail, "out": out,
                        "board": tap.rows if tap.count > published else None})
                i += 1
        window_s = time.perf_counter() - t0
        compiles = log.since(mark)
        gc.unfreeze()
        if trace:
            jax.profiler.stop_trace()
        # each counter's change over the window
        window_counts = {src: {k: v - counts0[src].get(k, 0)
                               for k, v in now.items()}
                         for src, now in counters(engine, dep.crm,
                                                  raylet).items()}
        memory_peak = _peak_bytes(used)
        record = {
            "cell": cell, "config": config, "traffic": traffic,
            "seed": seed, "trace": trace,
            "setup_s": t0 - t_start, "window_s": window_s,
            "round_s": round_s, "beat_s": beat_s,
            "beat_times": clock.times if clock else [],
            "offered": offered, "placed": placed,
            "counters": window_counts, "compiles": compiles,
            "setup_compiles": {k: setup_compiles[k] for k in
                               ("compiles", "compile_s", "cache_hits",
                                "cache_misses")},
            "samples": sampler.kept,
            "engine": ({"type": type(engine).__name__,
                        "shards": engine.stats.get("shards", 1)}
                       if engine is not None else None),
            "class_keys": dep.class_keys, "totals": dep.totals,
            "ref_vecs": dep.ref_vecs, "rounds": rounds,
            "module": loaded["module"],
            "device": {"platform": used[0].platform,
                       "kind": used[0].device_kind, "count": len(devices),
                       "memory_peak_bytes": memory_peak},
        }
    finally:
        if clock:
            clock.close()
        tap.close()
        dep.stop()
    if trace_dir:
        from . import xtrace
        try:
            record["trace_summary"] = xtrace.reduce(
                xtrace.load(trace_dir))
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)
    return record


def _profile_options():
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0    # the harness's spans are TraceMe's
    return opts


def end_to_end(record: dict) -> dict:
    """The end-to-end metrics, on the host clock, over the whole window."""
    ms = np.asarray(record["round_s"]) * 1e3
    return {
        "round_p50_ms": float(statistics.median(ms)),
        "round_p95_ms": float(np.percentile(ms, 95)),
        "placed_per_s": record["placed"] / record["window_s"],
        "setup_s": record["setup_s"],
    }


def per_layer(record: dict, metrics: list[dict]) -> dict:
    """Each per-layer metric from its own reader,
    ``benchmark/metrics/<name>.py``; a reader that finds nothing returns
    None and the metric is left out."""
    import importlib.util
    out = {}
    for m in metrics:
        path = BENCH / "metrics" / f"{m['name']}.py"
        spec = importlib.util.spec_from_file_location(
            f"benchmark.metrics.{m['name']}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        value = mod.read(record)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def setup_env() -> str:
    """Before JAX is imported: the compile cache inside the checkout, at a
    fixed path, for every program however quick to compile."""
    cache = str(ROOT / ".jax_cache")
    # JAX's size-capped cache (JAX_COMPILATION_CACHE_MAX_SIZE, set on the
    # chip machines) does not create its directory, and then caches nothing
    os.makedirs(cache, exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = cache
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    return cache
