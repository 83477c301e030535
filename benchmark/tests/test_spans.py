"""The program-span reduction (``benchmark/spans.py``) on a hand-built
two-chip trace whose answers are worked by hand, and on the trace
recorded on the chip, which holds no program span."""

from pathlib import Path

import pytest

from benchmark import spans
from benchmark import xtrace

DATA = Path(__file__).parent / "data" / "small_rounds_trace.json.gz"


def _chip(modules):
    return {"ops": [["%fusion.1", s, e - s] for s, e in modules],
            "modules": [["jit_beat", s, e - s] for s, e in modules]}


def _trace():
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
    return {"host": [[n, s, e - s] for n, s, e in host],
            "devices": {"/device:TPU:0": _chip([(240, 300), (740, 800)]),
                        "/device:TPU:1": _chip([(250, 300), (760, 800)]),
                        "/device:CUSTOM:Megascale Trace": _chip([])}}


def test_two_chips_by_hand():
    tr = _trace()
    got = spans.reduce(tr)
    # xtrace's own numbers stay as they are
    base = xtrace.reduce(tr)
    assert {k: got[k] for k in base if k != "idle_gaps"} == \
        {k: v for k, v in base.items() if k != "idle_gaps"}
    ns = 1e-9
    p = got["program_spans"]
    assert p["raylet.group"] == pytest.approx([70 * ns])   # 50 + 20
    assert p["delta.beat"] == pytest.approx([200 * ns, 260 * ns])
    assert p["delta.upload"] == pytest.approx([50 * ns, 100 * ns])
    assert p["raylet.expand"] == pytest.approx([80 * ns])
    assert p["raylet.publish"] == pytest.approx([80 * ns])
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
    m = spans.metrics(got, journal_scanned=300, beats=2)
    assert m["view_ms"] == pytest.approx(55e-6)
    assert m["group_ms"] == pytest.approx(70e-6)
    assert m["idle_explained_pct"] == pytest.approx(100 * 625 / 695)
    assert m["journal_scanned_per_beat"] == 150
    assert spans.reconcile(tr) == {"delta_beat_over_bench_beat": 1.0,
                                   "leaves_over_delta_beat": 1.0}


def test_no_program_spans_reads_as_before():
    tr = _trace()
    tr["host"] = [e for e in tr["host"] if e[0].startswith("bench.")]
    got = spans.reduce(tr)
    assert got["program_spans"] == {} and got["idle_explained_s"] == 0
    assert got["idle_gaps"] == xtrace.reduce(tr)["idle_gaps"]
    m = spans.metrics(got)
    assert m["view_ms"] is None and m["journal_scanned_per_beat"] is None
    assert m["idle_explained_pct"] == 0


def test_recorded_trace_labels_unchanged():
    tr = xtrace.read_saved(DATA)
    got = spans.reduce(tr)
    assert got["idle_gaps"] == xtrace.reduce(tr)["idle_gaps"]
    assert got["program_spans"] == {}
    assert 0 < got["idle_in_rounds_s"] <= got["window_s"]
