"""The program's own spans in a profiler trace: which host phase of the
placement round the chip waits on.

The program marks the phases of its round with profiler spans
(``ray_tpu.util.tracing.PHASES``): ``delta.beat`` around the engine's
``beat``, five ``delta.*`` leaves that cover it, and ``raylet.*`` leaves
for the raylet's host work around it.  ``load`` keeps of a trace what
``xtrace.load`` keeps, and those spans.  ``reduce`` returns
``xtrace.reduce``'s numbers and adds, over the window:

* ``program_spans``: per span name, its summed duration in each
  ``bench.round`` that holds it, in seconds (rounds without it are left
  out);
* ``idle_in_rounds_s``: per chip, the time inside ``bench.round``
  intervals in which no program or operation runs on it, averaged over
  the chips;
* ``idle_explained_s``: the part of that idle time that the union of
  the program's leaf spans (every span but ``delta.beat``) covers;
* ``idle_gaps``: ``xtrace``'s ten longest gaps, each label followed by
  ``": <leaf span>"``, the leaf with most time in the gap, where one
  touches it (unchanged where none does).

``metrics`` turns that into per-layer numbers.  The harness's own
reduction reads no program span yet, so

    python3 -m benchmark.spans --workload <name> --seed <n>

runs one traced window of a cell through the harness, on the chip as
``run.py`` does, and prints the numbers as one JSON line; with
``--cost`` it prints instead what one span costs with and without a
profiler session.
"""

from __future__ import annotations

import bisect
import glob
import json
import os
import statistics
import sys
import time

from . import xtrace

PREFIXES = ("delta.", "raylet.")
PARENTS = ("delta.beat",)


def program_events(log_dir: str) -> list:
    """[name, start_ns, duration_ns] of the program's spans in the newest
    ``*.xplane.pb`` under ``log_dir``."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no *.xplane.pb under {log_dir}")
    data = ProfileData.from_file(paths[-1])
    return [[e.name, e.start_ns, e.duration_ns]
            for plane in data.planes if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events
            if e.name.startswith(PREFIXES)]


def load(log_dir: str) -> dict:
    """``xtrace.load``'s compact trace, the program's spans among the
    host events."""
    tr = xtrace.load(log_dir)
    tr["host"] += program_events(log_dir)
    return tr


def _intersect(a, b):
    """Merged intervals ``a`` within merged intervals ``b``."""
    return xtrace._minus(a, xtrace._minus(a, b))


def per_round(tr: dict, names) -> dict:
    """{name: [summed duration in each window round holding it, s]}; a
    span belongs to the round in which it starts."""
    windows = [(s, s + d) for n, s, d in tr["host"] if n == "bench.window"]
    if not windows:
        return {}
    lo, hi = windows[0]
    rounds = sorted((s, s + d) for n, s, d in tr["host"]
                    if n == "bench.round" and lo <= s < hi)
    starts = [s for s, _ in rounds]
    sums: dict[str, dict[int, float]] = {}
    for n, s, d in tr["host"]:
        if n not in names:
            continue
        i = bisect.bisect_right(starts, s) - 1
        if i >= 0 and s < rounds[i][1]:
            by = sums.setdefault(n, {})
            by[i] = by.get(i, 0.0) + d * 1e-9
    return {n: [by[i] for i in sorted(by)] for n, by in sums.items()}


def reduce(tr: dict) -> dict:
    """``xtrace.reduce``'s numbers with the program's spans added (the
    module's docstring)."""
    out = xtrace.reduce(tr)
    if not out:
        return out
    lo, hi = [(s, s + d) for n, s, d in tr["host"]
              if n == "bench.window"][0]
    spans: dict[str, list] = {}
    for n, s, d in tr["host"]:
        spans.setdefault(n, []).append([s, s + d])
    spans = {k: xtrace._union(v) for k, v in spans.items()}
    leaves = sorted(n for n in spans
                    if n.startswith(PREFIXES) and n not in PARENTS)
    covered = xtrace._union([iv for n in leaves for iv in spans[n]])
    rounds = xtrace._clip(spans.get("bench.round", []), lo, hi)
    chips = [d for name, d in tr["devices"].items()
             if xtrace.CHIP_PLANE.match(name) and (d["ops"] or d["modules"])]
    idle_in = explained = 0.0
    gaps = []
    for dev in chips:
        busy = xtrace._union(xtrace._clip(
            [[s, s + d] for _, s, d in dev["modules"] + dev["ops"]],
            lo, hi))
        idle = xtrace._minus([[lo, hi]], busy)
        gaps += idle
        in_rounds = _intersect(idle, rounds)
        idle_in += xtrace._length(in_rounds)
        explained += xtrace._length(_intersect(in_rounds, covered))
    gaps.sort(key=lambda g: g[0] - g[1])
    ns = 1e-9
    out["program_spans"] = per_round(tr, set(n for n in spans
                                             if n.startswith(PREFIXES)))
    out["idle_in_rounds_s"] = idle_in / len(chips) * ns
    out["idle_explained_s"] = explained / len(chips) * ns
    out["idle_gaps"] = [[_label(spans, leaves, s, e), (e - s) * ns]
                        for s, e in gaps[:10]]
    return out


def _label(spans: dict, leaves: list, lo: float, hi: float) -> str:
    """``xtrace``'s label of the gap, and the program's leaf span with
    most time in it, where one touches it."""
    label = xtrace._host_label(spans, lo, hi)
    t, leaf = max(((xtrace._overlap(spans[n], lo, hi), n) for n in leaves),
                  default=(0, None))
    return f"{label}: {leaf}" if t > 0 else label


# span -> per-layer metric: the median, over the window's rounds that
# hold the span, of its summed duration in the round (ms)
SPAN_METRICS = {"view_ms": "delta.view", "upload_ms": "delta.upload",
                "stage_ms": "delta.stage", "dispatch_ms": "delta.dispatch",
                "readback_wait_ms": "delta.readback",
                "group_ms": "raylet.group", "expand_ms": "raylet.expand",
                "publish_ms": "raylet.publish"}


def metrics(summary: dict, journal_scanned: int | None = None,
            beats: int | None = None) -> dict:
    """Per-layer numbers from ``reduce``'s summary (a metric is None
    where nothing was there to read)."""
    spans = summary.get("program_spans") or {}
    out = {m: (statistics.median(spans[n]) * 1e3 if spans.get(n) else None)
           for m, n in SPAN_METRICS.items()}
    idle = summary.get("idle_in_rounds_s")
    out["idle_explained_pct"] = (100.0 * summary["idle_explained_s"] / idle
                                 if idle else None)
    out["journal_scanned_per_beat"] = (journal_scanned / beats
                                       if journal_scanned and beats
                                       else None)
    return out


def reconcile(tr: dict) -> dict:
    """The spans against the harness's outside timing over the window:
    summed ``delta.beat`` ÷ summed ``bench.beat``, and the five
    ``delta.*`` leaves summed ÷ summed ``delta.beat``."""
    names = {n for n, _, _ in tr["host"]
             if n == "bench.beat" or n.startswith("delta.")}
    tot = {n: sum(v) for n, v in per_round(tr, names).items()}
    beat = tot.get("delta.beat", 0.0)
    leaves = sum(v for n, v in tot.items()
                 if n.startswith("delta.") and n not in PARENTS)
    return {"delta_beat_over_bench_beat":
            beat / tot["bench.beat"] if tot.get("bench.beat") else None,
            "leaves_over_delta_beat": leaves / beat if beat else None}


def span_cost(n: int = 200_000) -> dict:
    """Microseconds per ``with phase(...)``: with no profiler session,
    with one, and a ``nullcontext`` for scale (host clock)."""
    import contextlib
    import tempfile

    import jax

    from ray_tpu.util.tracing import phase

    def per(make) -> float:
        t0 = time.perf_counter()
        for _ in range(n):
            with make("delta.view"):
                pass
        return (time.perf_counter() - t0) / n * 1e6

    null = contextlib.nullcontext
    out = {"null_us": per(lambda _n: null()), "inactive_us": per(phase)}
    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d)
        try:
            out["active_us"] = per(phase)
        finally:
            jax.profiler.stop_trace()
    return out


def run(workload: str, seed: int, seconds: float,
        platform: str | None = "tpu", loaded: dict | None = None) -> dict:
    """One traced window of the cell through ``harness.run_cell``, the
    program's spans kept: what ``main`` prints."""
    from ray_tpu.util.tracing import PHASES

    from . import check, harness

    loaded = loaded or harness.load_cell(workload)
    kept: dict = {}
    scanned: dict = {}

    def load_all(log_dir):
        kept["trace"] = tr = orig_load(log_dir)
        tr["host"] += program_events(log_dir)
        return tr

    class Clock(harness.BeatClock):
        """The harness's clock, and the CRM's journal count over the
        window (the clock is made just before the window, closed just
        after it)."""

        def __init__(self, engine, span):
            super().__init__(engine, span)
            self.frozen = engine._crm.frozen_stats
            self.scanned0 = self.frozen.get("journal_scanned", 0)

        def close(self):
            scanned["n"] = (self.frozen.get("journal_scanned", 0)
                            - self.scanned0)
            super().close()

    orig_load, orig_clock = xtrace.load, harness.BeatClock
    xtrace.load, harness.BeatClock = load_all, Clock
    try:
        record = harness.run_cell(loaded, seed, seconds, True,
                                  platform=platform)
    finally:
        xtrace.load, harness.BeatClock = orig_load, orig_clock
    tr = kept["trace"]
    summary = reduce(tr)
    checks = check.judge(record)
    beats = record["engine_stats"].get("beats")
    return {"workload": workload, "seed": seed,
            "correct": check.passed(checks, record),
            "rounds": len(record["round_s"]),
            "round_p50_ms": statistics.median(record["round_s"]) * 1e3,
            "metrics": metrics(summary, scanned.get("n"), beats),
            "span_ms": {n: statistics.median(v) * 1e3 for n, v in
                        per_round(tr, PHASES).items()},
            "harness_metrics": {k: v["value"] for k, v in harness.per_layer(
                record, loaded["per_layer"]).items()},
            "reconcile": reconcile(tr),
            "journal_scanned": scanned.get("n"), "beats": beats,
            "idle_in_rounds_s": summary.get("idle_in_rounds_s"),
            "idle_explained_s": summary.get("idle_explained_s"),
            "window_s": summary.get("window_s"),
            "busy_s": summary.get("busy_s"),
            "idle_gaps": summary.get("idle_gaps"),
            "device": record["device"]}


def main(argv: list[str]) -> int:
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload")
    ap.add_argument("--seed", type=int)
    ap.add_argument("--cost", action="store_true")
    args = ap.parse_args(argv)
    if not args.cost and (args.workload is None or args.seed is None):
        ap.error("--workload and --seed are required without --cost")
    from . import harness
    harness.setup_env()
    if args.cost:
        out = span_cost()
    else:
        out = run(args.workload, args.seed, harness.TRACE_SECONDS)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
