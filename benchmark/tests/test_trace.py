"""The trace reduction on a small trace recorded on the chip
(``data/small_rounds_trace.json.gz``: a 2 s traced window of a cell of
host rounds with a 4,096-task burst every 32nd, since withdrawn, as the
reduction kept it then: the harness's spans, no program span), and on
hand-built two-chip traces whose answers are worked by hand; then the
per-layer readers on a record built by hand around such a trace."""

import json
from pathlib import Path

import pytest

from benchmark import harness
from benchmark import xtrace as trace

DATA = Path(__file__).parent / "data" / "small_rounds_trace.json.gz"


def test_recorded_trace():
    tr = trace.read_saved(DATA)
    # the profiler's Megascale plane is no chip
    assert set(tr["devices"]) == {"/device:TPU:0",
                                  "/device:CUSTOM:Megascale Trace"}
    got = trace.reduce(tr)
    (lo, dur), = [(s, d) for n, s, d in tr["host"] if n == "bench.window"]
    hi = lo + dur
    chip = tr["devices"]["/device:TPU:0"]
    modules = [(s, s + d) for _, s, d in chip["modules"] if lo <= s < hi]
    assert len(modules) == 12       # 6 bursts: a full rescore and a beat
    kernel = sum(min(e, hi) - max(s, lo) for s, e in modules)
    assert got["kernel_s"] == pytest.approx(kernel * 1e-9)
    assert got["window_s"] == pytest.approx(dur * 1e-9)
    # every op of this trace runs inside a program
    assert got["busy_s"] == pytest.approx(got["kernel_s"])
    assert got["collective_ops"] == 0 and got["collective_exposed_s"] == 0
    assert 0 < got["busy_s"] < 0.01 * got["window_s"]
    gaps = [g for _, g in got["idle_gaps"]]
    assert gaps == sorted(gaps, reverse=True) and len(gaps) == 10
    assert got["idle_gaps"][0][0] == "raylet round, outside beat"
    assert got["device_ops"][0][0] == "%while.39"


def _chip(ops, modules):
    return {"ops": [[n, s, e - s] for n, s, e in ops],
            "modules": [["jit_beat", s, e - s] for s, e in modules]}


def test_two_chips_by_hand():
    tr = {"host": [["bench.window", 0, 1000], ["bench.round", 0, 900],
                   ["bench.beat", 100, 700]],
          "devices": {
              "/device:TPU:0": _chip(
                  [["%while.1", 100, 500], ["%fusion.1", 100, 200],
                   ["%psum.1", 200, 300], ["%fusion.2", 250, 260],
                   ["%all-gather.2", 400, 450]],
                  [(100, 500)]),
              "/device:TPU:1": _chip(
                  [["%fusion.1", 300, 400], ["%all-reduce.1", 350, 450]],
                  [(300, 600)]),
              "/device:CUSTOM:Megascale Trace": _chip([], [])}}
    got = trace.reduce(tr)
    # chip 0: busy 100-500 = 400, kernel 400; exposed = (200-300 less
    # 250-260) + 400-450 = 90 + 50 = 140
    # chip 1: busy 300-600 = 300, kernel 300; exposed = 400-450 = 50
    assert got["busy_s"] == pytest.approx(350e-9)
    assert got["kernel_s"] == pytest.approx(350e-9)
    assert got["collective_exposed_s"] == pytest.approx(95e-9)
    assert got["collective_ops"] == 3
    # gaps: chip 0 [0,100) [500,1000); chip 1 [0,300) [600,1000)
    assert [g for _, g in got["idle_gaps"]] == pytest.approx(
        [500e-9, 400e-9, 300e-9, 100e-9])
    # [500,1000): beat 200 (500-700), round 200 (700-900), harness 100
    assert got["idle_gaps"][0][0] == "host inside beat"
    # no program span: no leaf to name, no idle time explained
    assert got["program_spans"] == {"bench.beat": [pytest.approx(700e-9)]}
    assert got["idle_explained_s"] == 0


def test_recorded_trace_has_no_program_spans():
    tr = trace.read_saved(DATA)
    got = trace.reduce(tr)
    assert set(got["program_spans"]) <= {"bench.beat"}
    assert got["idle_explained_s"] == 0
    assert 0 < got["idle_in_rounds_s"] <= got["window_s"]


def test_span_rule():
    for name in ("bench.window", "delta.upload", "raylet.group",
                 "raylet.new_phase", "a.b.c"):
        assert trace.SPAN_NAME.match(name), name
    for name in ("Thunk::Execute", "jit_beat", "delta", "Delta.view",
                 "delta.", ".view", "delta view", "$core.py:1 x"):
        assert not trace.SPAN_NAME.match(name), name


def _spans_trace():
    """Two rounds, R1 [0, 400) and R2 [500, 900), in a window [0, 1000).
    R1 groups (two intervals), beats [100, 300) and expands; R2 builds
    overrides, beats [540, 800) and publishes."""
    beat1 = [["delta.view", 100, 150], ["delta.upload", 150, 200],
             ["delta.stage", 200, 220], ["delta.dispatch", 220, 240],
             ["delta.readback", 240, 300]]
    beat2 = [["delta.view", 540, 600], ["delta.upload", 600, 700],
             ["delta.stage", 700, 720], ["delta.dispatch", 720, 740],
             ["delta.readback", 740, 800]]
    host = ([["bench.window", 0, 1000], ["bench.round", 0, 400],
             ["bench.round", 500, 900], ["bench.churn", 400, 500],
             ["bench.churn", 900, 990], ["bench.beat", 100, 300],
             ["bench.beat", 540, 800], ["raylet.group", 10, 60],
             ["raylet.group", 80, 100], ["delta.beat", 100, 300],
             ["raylet.expand", 300, 380], ["raylet.overrides", 500, 540],
             ["delta.beat", 540, 800], ["raylet.publish", 800, 880]]
            + beat1 + beat2)
    modules = {"/device:TPU:0": [(240, 300), (740, 800)],
               "/device:TPU:1": [(250, 300), (760, 800)],
               "/device:CUSTOM:Megascale Trace": []}
    return {"host": [[n, s, e - s] for n, s, e in host],
            "devices": {k: _chip([["%fusion.1", s, e] for s, e in v], v)
                        for k, v in modules.items()}}


def test_program_spans_by_hand():
    tr = _spans_trace()
    got = trace.reduce(tr)
    ns = 1e-9
    p = got["program_spans"]
    assert p["raylet.group"] == pytest.approx([70 * ns])   # 50 + 20
    assert p["delta.beat"] == pytest.approx([200 * ns, 260 * ns])
    assert p["delta.upload"] == pytest.approx([50 * ns, 100 * ns])
    assert p["raylet.expand"] == pytest.approx([80 * ns])
    assert p["raylet.publish"] == pytest.approx([80 * ns])
    assert "bench.churn" not in p and "bench.window" not in p
    # chip 0 idle in rounds: [0,240) [300,400) [500,740) [800,900) = 680,
    # of it under a leaf ([10,60) [80,380) [500,880)): 210+80+240+80 = 610
    # chip 1: [0,250) [300,400) [500,760) [800,900) = 710; 220+80+260+80
    assert got["idle_in_rounds_s"] == pytest.approx(695 * ns)
    assert got["idle_explained_s"] == pytest.approx(625 * ns)
    # gaps by length: chip 1 [300,760), chip 0 [300,740): 220 / 200 of
    # beat, most of it delta.upload (100); [0,250), [0,240): the beat
    # (150 / 140) leads, raylet.group (70) the leaves; [800,1000): the
    # round (100) against churn (90), raylet.publish (80)
    assert got["idle_gaps"] == [
        ["host inside beat: delta.upload", pytest.approx(460 * ns)],
        ["host inside beat: delta.upload", pytest.approx(440 * ns)],
        ["host inside beat: raylet.group", pytest.approx(250 * ns)],
        ["host inside beat: raylet.group", pytest.approx(240 * ns)],
        ["raylet round, outside beat: raylet.publish",
         pytest.approx(200 * ns)],
        ["raylet round, outside beat: raylet.publish",
         pytest.approx(200 * ns)]]
    record = {"trace_summary": got}
    assert trace.span_ms(record, "delta.view") == pytest.approx(55e-6)
    assert trace.span_ms(record, "raylet.group") == pytest.approx(70e-6)
    assert trace.span_ms(record, "raylet.absent") is None
    assert trace.reconcile(got) == {"delta_beat_over_bench_beat": 1.0,
                                    "leaves_over_delta_beat": 1.0}


def test_a_new_span_is_read_by_its_name():
    """A span the program adds reaches the reduction with no edit: a
    leaf inside ``raylet.expand`` makes it a parent."""
    tr = _spans_trace()
    tr["host"].append(["raylet.argsort", 310, 30])
    got = trace.reduce(tr)
    assert got["program_spans"]["raylet.argsort"] == pytest.approx([30e-9])
    # [300,310) and [340,380) of raylet.expand no longer count as leaf
    # time: chip 0 and chip 1 each lose 50 of explained idle time
    assert got["idle_explained_s"] == pytest.approx(575e-9)


def test_no_program_spans_reads_as_before():
    tr = _spans_trace()
    tr["host"] = [e for e in tr["host"] if e[0].startswith("bench.")]
    got = trace.reduce(tr)
    assert set(got["program_spans"]) == {"bench.beat"}
    assert got["idle_explained_s"] == 0
    assert [g[0] for g in got["idle_gaps"]] == [
        "host inside beat", "host inside beat", "host inside beat",
        "host inside beat", "raylet round, outside beat",
        "raylet round, outside beat"]
    assert trace.span_ms({"trace_summary": got}, "delta.view") is None


def test_every_phase_follows_the_span_rule():
    """The harness keeps the program's spans by ``SPAN_NAME``, not by a
    list: every name of ``PHASES`` has to follow it."""
    from ray_tpu.util.tracing import PHASES
    for name in PHASES:
        assert trace.SPAN_NAME.match(name), name


def _per_layer(record):
    """Every per-layer metric of ``BENCHMARK.json`` but the roofline
    share (``test_roofline.py``), through the harness's readers."""
    spec = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    metrics = [m for m in spec["per_layer"] if m["name"] != "beat_roofline"]
    return {k: v["value"] for k, v in
            harness.per_layer(record, metrics).items()}


def _record(summary, engine=None, crm=None):
    """A hand-built run record: the two rounds of ``_spans_trace``, each
    [0, 400) long, with their beats."""
    ns = 1e-9
    return {"trace_summary": summary,
            "counters": {"engine": engine or {}, "crm": crm or {},
                         "raylet": {}},
            "round_s": [400 * ns, 400 * ns],
            "beat_s": [200 * ns, 260 * ns] if engine else [],
            "beat_times": [200 * ns, 260 * ns] if engine else [],
            "compiles": {"compiles": 0}, "cell": {"chips": 2}}


def test_readers_on_a_hand_built_record():
    got = _per_layer(_record(trace.reduce(_spans_trace()),
                             engine={"beats": 2, "full_rescores": 0},
                             crm={"journal_scanned": 300}))
    us = 1e-6      # a nanosecond in ms
    assert got == pytest.approx({
        # chip 0 runs programs 120 of the window's 1000, chip 1 90
        "kernel_ms": 105 / 2 * us, "device_idle_pct": 100 * (1 - 0.105),
        "idle_explained_pct": 100 * 625 / 695,
        "journal_scanned_per_beat": 150, "delta_hit_pct": 100,
        "view_ms": 55 * us, "upload_ms": 75 * us, "stage_ms": 20 * us,
        "dispatch_ms": 20 * us, "readback_wait_ms": 60 * us,
        "group_ms": 70 * us, "expand_ms": 80 * us, "publish_ms": 80 * us,
        "beat_ms": 230 * us, "raylet_host_ms": 170 * us,
        "compiles_in_window": 0})
    # no collective op in the trace: nothing to read, no 0
    assert "collective_exposed_ms" not in got


def test_readers_with_nothing_to_read():
    """No program span: the span metrics are left out, and none of the
    idle time is explained; no trace and no engine: only what the host
    clock and the compile log read."""
    tr = _spans_trace()
    tr["host"] = [e for e in tr["host"] if e[0].startswith("bench.")]
    got = _per_layer(_record(trace.reduce(tr), engine={"beats": 2,
                                                       "full_rescores": 1}))
    assert got["idle_explained_pct"] == 0 and got["delta_hit_pct"] == 50
    assert not {"view_ms", "group_ms", "publish_ms",
                "journal_scanned_per_beat"} & set(got)
    got = _per_layer(_record({}))
    assert got == pytest.approx({"raylet_host_ms": 400e-6,
                                 "compiles_in_window": 0})
