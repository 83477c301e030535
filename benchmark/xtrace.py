"""From a profiler trace to the device's busy time, the beat's kernel time,
its exposed collectives and the program's spans.

``load`` keeps of a JAX profiler trace (``*.xplane.pb``) only what the
reduction reads: per device, the intervals of its XLA operations and of
its XLA programs (modules); on the host, every span whose name follows
the program's span rule, a lower-case dotted name (``SPAN_NAME``): the
harness's own (``bench.*``) and the program's (``ray_tpu.util.tracing``
``PHASES``, such as ``raylet.group`` and ``delta.upload``), so that a
span a later change adds reaches the record as it is.  ``reduce`` turns
that into numbers over the window that the host span ``bench.window``
marks:

* ``busy_s``: per chip, the union of the intervals in which a program
  or an operation runs on it (a program holds the chip between the
  operations of its loops too), averaged over the chips; ``window_s``
  the window's length;
* ``kernel_s``: per device, the summed duration of its programs, averaged
  over the devices: the beat programs are the only ones the window runs;
* ``collective_exposed_s``: per device, the part of its collective
  operations (all-reduce, all-gather, ...) during which no other
  operation runs on it (loop and call ops, which only hold others, do
  not count as running), averaged over the devices;
* ``device_ops``: the ten operations that took most time, and
  ``idle_gaps``: the ten longest gaps between operations, each named by
  what the harness was doing on the host then and, after ``": "``, the
  program's leaf span with most time in the gap, where one touches it;
* ``program_spans``: per span (every one but ``bench.window`` and
  ``bench.round``), its summed duration in each window round that holds
  it, in seconds; a span belongs to the round in which it starts, and
  rounds without it are left out;
* ``idle_in_rounds_s``: per chip, the time inside ``bench.round`` in
  which no program or operation runs on it, averaged over the chips, and
  ``idle_explained_s``, the part of it that the union of the program's
  leaf spans (those that hold no other program span) covers.
"""

from __future__ import annotations

import bisect
import glob
import gzip
import json
import os
import re
import statistics

# op names as the TPU trace gives them: XLA's collective ops, and the
# shard_map primitives they are lowered from (``%psum.53``, ``%pmin.31``)
COLLECTIVES = ("all-reduce", "all-gather", "collective-permute",
               "reduce-scatter", "all-to-all", "psum", "pmin", "pmax",
               "ppermute")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
# loop and call ops span the operations they run: no compute of their own
CONTAINERS = ("while", "conditional", "call")
# chips, not the profiler's other "/device:" planes (e.g. CUSTOM:Megascale)
CHIP_PLANE = re.compile(r"^/device:TPU:\d+$")
# the program's span rule: lower-case words joined by dots
SPAN_NAME = re.compile(r"^[a-z][a-z0-9_]*(\.[a-z][a-z0-9_]*)+$")


def load(log_dir: str) -> dict:
    """The compact trace of the newest ``*.xplane.pb`` under ``log_dir``."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no *.xplane.pb under {log_dir}")
    data = ProfileData.from_file(paths[-1])
    out = {"devices": {}, "host": []}
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            dev = out["devices"].setdefault(plane.name,
                                            {"ops": [], "modules": []})
            for line in plane.lines:
                key = {OPS_LINE: "ops", MODULES_LINE: "modules"}.get(
                    line.name)
                if key:
                    dev[key] += [[e.name.split(" = ")[0], e.start_ns,
                                  e.duration_ns] for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                out["host"] += [[e.name, e.start_ns, e.duration_ns]
                                for e in line.events
                                if SPAN_NAME.match(e.name)]
    return out


def save(tr: dict, path: str) -> None:
    with gzip.open(path, "wt") as f:
        json.dump(tr, f)


def read_saved(path: str) -> dict:
    with gzip.open(path, "rt") as f:
        return json.load(f)


def _union(intervals):
    """Sorted, merged [start, end) intervals."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        elif e > s:
            merged.append([s, e])
    return merged


def _length(intervals) -> float:
    return sum(e - s for s, e in intervals)


def _clip(intervals, lo, hi):
    return [[max(s, lo), min(e, hi)] for s, e in intervals
            if min(e, hi) > max(s, lo)]


def _minus(a, b):
    """Merged intervals ``a`` less merged intervals ``b``."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append([cur, b[k][0]])
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append([cur, e])
    return out


def _intersect(a, b):
    """Merged intervals ``a`` within merged intervals ``b``."""
    return _minus(a, _minus(a, b))


def _overlap(intervals, lo, hi) -> float:
    return _length(_clip(intervals, lo, hi))


def _host_label(spans: dict, lo: float, hi: float) -> str:
    """What the harness was doing on the host during [lo, hi): the span
    kind with the most time there, counting a round's time inside its
    beat as the beat's."""
    beat = _overlap(spans.get("bench.beat", []), lo, hi)
    rnd = _overlap(spans.get("bench.round", []), lo, hi) - beat
    churn = _overlap(spans.get("bench.churn", []), lo, hi)
    other = (hi - lo) - beat - rnd - churn
    return max((beat, "host inside beat"), (rnd, "raylet round, outside beat"),
               (churn, "churn between rounds"), (other, "harness"))[1]


def _label(spans: dict, leaves: list, lo: float, hi: float) -> str:
    """``_host_label`` of the gap, and the program's leaf span with most
    time in it, where one touches it."""
    label = _host_label(spans, lo, hi)
    t, leaf = max(((_overlap(spans[n], lo, hi), n) for n in leaves),
                  default=(0, None))
    return f"{label}: {leaf}" if t > 0 else label


def _leaves(spans: dict, names: list) -> list:
    """Those of ``names`` whose spans hold no span of another of them."""
    def holds(outer, inner):
        starts = [s for s, _ in outer]
        for s, e in inner:
            i = bisect.bisect_right(starts, s) - 1
            if i >= 0 and e <= outer[i][1]:
                return True
        return False
    return [n for n in names
            if not any(holds(spans[n], spans[m]) for m in names if m != n)]


def _per_round(host: list, lo: float, hi: float, names) -> dict:
    """{name: [summed duration in each window round holding it, s]}."""
    rounds = sorted((s, s + d) for n, s, d in host
                    if n == "bench.round" and lo <= s < hi)
    starts = [s for s, _ in rounds]
    sums: dict[str, dict[int, float]] = {}
    for n, s, d in host:
        if n not in names:
            continue
        i = bisect.bisect_right(starts, s) - 1
        if i >= 0 and s < rounds[i][1]:
            by = sums.setdefault(n, {})
            by[i] = by.get(i, 0.0) + d * 1e-9
    return {n: [by[i] for i in sorted(by)] for n, by in sums.items()}


def span_ms(record: dict, name: str) -> float | None:
    """Median over the traced window's rounds that hold span ``name`` of
    its summed duration in the round, in ms; None where none holds it."""
    summary = record.get("trace_summary") or {}
    times = (summary.get("program_spans") or {}).get(name)
    return statistics.median(times) * 1e3 if times else None


def reconcile(summary: dict) -> dict:
    """The program's spans against the harness's own timing over the
    window, from ``reduce``'s ``program_spans``: summed ``delta.beat``
    over summed ``bench.beat``, and the ``delta.*`` leaves summed over
    ``delta.beat``; None where a sum is 0."""
    tot = {n: sum(v) for n, v in
           (summary.get("program_spans") or {}).items()}
    beat = tot.get("delta.beat", 0.0)
    leaves = sum(v for n, v in tot.items()
                 if n.startswith("delta.") and n != "delta.beat")
    return {"delta_beat_over_bench_beat":
            beat / tot["bench.beat"] if tot.get("bench.beat") else None,
            "leaves_over_delta_beat": leaves / beat if beat else None}


def reduce(tr: dict) -> dict:
    """Numbers over the window marked by the host span ``bench.window``."""
    windows = [(s, s + d) for n, s, d in tr["host"] if n == "bench.window"]
    if not windows:
        return {}
    lo, hi = windows[0]
    spans: dict[str, list] = {}
    for n, s, d in tr["host"]:
        spans.setdefault(n, []).append([s, s + d])
    spans = {k: _union(v) for k, v in spans.items()}
    chips = [d for name, d in tr["devices"].items()
             if CHIP_PLANE.match(name) and (d["ops"] or d["modules"])]
    if not chips:
        return {}
    leaves = _leaves(spans, sorted(n for n in spans
                                   if not n.startswith("bench.")))
    covered = _union([iv for n in leaves for iv in spans[n]])
    rounds = _clip(spans.get("bench.round", []), lo, hi)
    n_dev = len(chips)
    busy = kernel = exposed = idle_in = explained = 0.0
    op_time: dict[str, float] = {}
    gaps = []
    for dev in chips:
        ops = [[n.split(" = ")[0], s, s + d] for n, s, d in dev["ops"]]
        modules = _clip([[s, s + d] for _, s, d in dev["modules"]], lo, hi)
        busy_iv = _union(modules + _clip([[s, e] for _, s, e in ops],
                                         lo, hi))
        busy += _length(busy_iv)
        kernel += _length(modules)
        coll = _union(_clip([[s, e] for n, s, e in ops
                             if n.lstrip("%").startswith(COLLECTIVES)],
                            lo, hi))
        rest = _union(_clip([[s, e] for n, s, e in ops
                             if not n.lstrip("%").startswith(
                                 COLLECTIVES + CONTAINERS)], lo, hi))
        exposed += _length(_minus(coll, rest))
        for n, s, e in ops:
            t = min(e, hi) - max(s, lo)
            if t > 0:
                op_time[n] = op_time.get(n, 0.0) + t / n_dev
        idle = _minus([[lo, hi]], busy_iv)
        gaps += idle
        in_rounds = _intersect(idle, rounds)
        idle_in += _length(in_rounds)
        explained += _length(_intersect(in_rounds, covered))
    gaps.sort(key=lambda g: g[0] - g[1])
    ns = 1e-9
    return {
        "busy_s": busy / n_dev * ns,
        "window_s": (hi - lo) * ns,
        "kernel_s": kernel / n_dev * ns,
        "collective_exposed_s": exposed / n_dev * ns,
        "collective_ops": sum(1 for dev in chips for n, s, d in dev["ops"]
                              if n.lstrip("%").startswith(COLLECTIVES)
                              and lo <= s < hi),
        "device_ops": [[n, t * ns] for n, t in
                       sorted(op_time.items(), key=lambda x: -x[1])[:10]],
        "idle_gaps": [[_label(spans, leaves, s, e), (e - s) * ns]
                      for s, e in gaps[:10]],
        "program_spans": _per_round(
            tr["host"], lo, hi,
            set(spans) - {"bench.window", "bench.round"}),
        "idle_in_rounds_s": idle_in / n_dev * ns,
        "idle_explained_s": explained / n_dev * ns,
    }
