"""The shuffle deployment (``shuffle_256.locality``): a small replay
through ``run_cell`` and ``judge``, the soft-affinity reference on cases
worked by hand, the localized beat's bytes, the three readers it brings,
and that the deployment is its own files only."""

import json

import numpy as np
import pytest

from benchmark import check, harness
from benchmark.locality_roofline import localized_beat_bytes
from benchmark.peaks import peaks
from benchmark.reference import locality as ref
from benchmark.roofline import beat_bytes

SHUFFLE_FILES = {"configs/shuffle_256.json", "traffic/locality.json",
                 "rounds/shuffle.py", "reference/locality.py",
                 "locality_roofline.py", "metrics/locality_ms.py",
                 "metrics/groups_per_beat.py",
                 "metrics/locality_beat_roofline.py",
                 "tests/test_shuffle.py"}
NEW_METRICS = ("locality_ms", "groups_per_beat", "locality_beat_roofline")


def _small(loaded):
    """The cell at a small size: 64 nodes, 12 data nodes, 4 mappers a
    stage, rounds of 512 tasks over a device floor of 256."""
    loaded["config"].update(nodes=64, data_nodes=12, mappers_per_stage=4)
    loaded["traffic"].update(round_tasks=512, pool_batches=2,
                             sample_rounds=3)
    loaded["traffic"]["system_config"] = {"scheduler_device_batch_min": 256}
    return loaded


def test_small_replay_correct_and_control_fails():
    loaded = _small(harness.load_cell("shuffle_256.locality"))
    record = harness.run_cell(loaded, 2**31 + 29, 0.5, False, platform=None)
    assert record["rounds"].__class__.__module__ == "benchmark.rounds.shuffle"
    checks = check.judge(record)
    assert set(checks) == set(check.LIMITS)
    assert check.passed(checks, record), checks
    assert not check.passed(check.judge(record, produce=check.control),
                            record)
    engine = record["counters"]["engine"]
    rounds = len(record["round_s"])
    assert rounds > 0 and record["offered"] == 512 * rounds
    # every round took the localized beat: each stage's 4 map classes and
    # (4 reduce classes x 4 mapper rows) subgroups
    assert engine["beats"] == rounds
    assert engine["groups"] == 20 * rounds
    assert engine["pref_groups"] == 16 * rounds
    assert record["counters"]["raylet"]["group_probed"] == 256 * rounds


def test_tie_goes_to_the_lowest_row():
    rows = np.array([[5, 2, 9, -1], [-1, -1, -1, -1], [7, 3, 7, 3]])
    sizes = np.array([[10, 10, 4, 0], [0, 0, 0, 0], [1, 2, 1, 3]])
    # 5 and 2 tie at 10 bytes; no chunks; 3 holds 5 bytes against 7's 2
    assert ref.preferred_rows(rows, sizes).tolist() == [2, -1, 3]


def _cluster():
    """Three nodes of (CPU, memory) centi-units; node 1 too small for
    class 1."""
    totals = np.array([[800, 800], [100, 800], [400, 400]])
    avail = np.array([[800, 800], [100, 800], [400, 400]])
    reqs = np.array([[100, 0], [200, 100]])
    return totals, avail, reqs


def test_infeasible_preferred_row_falls_back():
    totals, avail, reqs = _cluster()
    # class 1 prefers node 1, which has 100 of the 200 CPU it needs
    placed, rows, after = ref.schedule(totals, avail, reqs, [1, 1],
                                       [1, 1], 0.5)
    want, want_after = ref.oracle.schedule_grouped(
        totals, avail, np.ones(3, bool), reqs[1:], [2], 0.5)
    np.testing.assert_array_equal(placed, want)
    np.testing.assert_array_equal(after, want_after)
    assert placed[0, 1] == 0 and rows.tolist() == [0, 0]


def test_exhausted_preferred_row_queues():
    totals, avail, reqs = _cluster()
    # five class-1 tasks prefer node 2, which fits two (400 // 200): all
    # five land there, two debit it, three queue; then two plain class-0
    # tasks water-fill against what is left
    placed, rows, after = ref.schedule(
        totals, avail, reqs, [1, 1, 1, 1, 1, 0, 0], [2] * 5 + [-1] * 2,
        0.5)
    assert placed[0].tolist() == [0, 0, 5, 0]
    np.testing.assert_array_equal(after[2], [0, 200])
    want, _ = ref.oracle.schedule_grouped(
        totals, np.array([[800, 800], [100, 800], [0, 200]]),
        np.ones(3, bool), reqs[:1], [2], 0.5)
    np.testing.assert_array_equal(placed[1], want[0])
    assert rows.tolist()[:5] == [2] * 5


def test_control_breaks_the_carry():
    totals, avail, reqs = _cluster()
    classes, prefs = [1, 1, 1, 0, 0], [0, 0, 0, -1, -1]
    got = ref.schedule(totals, avail, reqs, classes, prefs, 0.5)
    ctl = ref.schedule(totals, avail, reqs, classes, prefs, 0.5,
                       carry=False)
    # with the carry class 0 sees node 0 past the spread threshold and
    # goes to node 2; the control sees node 0 empty
    assert not np.array_equal(got[0], ctl[0])


def test_localized_beat_bytes_by_hand():
    # shuffle_256: N=256, R=3, C=8, G=68 groups, d=8 dirty rows:
    #   beat_bytes              = 8*256*3 + 256 + 4*68*3 + 4*68
    #                             + 8*25 + 4*68*257 + 4*8*256
    #                           = 6,144 + 256 + 816 + 272 + 200
    #                             + 69,904 + 8,192 = 85,784
    #   preferred rows and flags 68*(5 + 8*3) = 1,972
    assert beat_bytes(256, 3, 68, 8, 8) == 85_784
    assert localized_beat_bytes(256, 3, 68, 8, 8) == 87_756
    assert localized_beat_bytes(256, 3, 0, 8, 8) == beat_bytes(
        256, 3, 0, 8, 8)


def _read(name, record):
    return harness.per_layer(record, [{"name": name, "unit": "x"}]).get(
        name, {}).get("value")


def _record(engine, spans=None, kernel_s=None):
    return {"config": json.loads(
                (harness.BENCH / "configs" / "shuffle_256.json").read_text()),
            "cell": {"chips": 1}, "round_s": [0.07] * 4,
            "device": {"kind": "TPU v5 lite"},
            "counters": {"engine": engine, "crm": {}, "raylet": {}},
            "trace_summary": {"program_spans": spans or {},
                              "kernel_s": kernel_s}}


def test_readers_by_hand():
    engine = {"beats": 4, "groups": 272, "pref_groups": 256,
              "rows_uploaded": 32}
    spans = {"raylet.locality": [0.060, 0.064, 0.062, 0.070]}
    rec = _record(engine, spans, kernel_s=4 * 5e-3)
    assert _read("locality_ms", rec) == pytest.approx(63.0)
    assert _read("groups_per_beat", rec) == pytest.approx(68.0)
    # 87,756 bytes at 819 GB/s over 5 ms of device time a beat
    want = 100 * 87_756 / peaks("TPU v5 lite")["hbm_bytes_per_s"] / 5e-3
    assert _read("locality_beat_roofline", rec) == pytest.approx(want)


def test_readers_find_nothing_on_a_program_without_them():
    # a program that takes no beat in these rounds, keeps no group
    # counter and has no locality span: every reader returns nothing
    for engine in ({}, {"beats": 3, "full_rescores": 0}):
        rec = _record(engine, {"raylet.group": [0.002]}, kernel_s=1e-3)
        for name in NEW_METRICS:
            assert _read(name, rec) is None, (name, engine)


def test_the_deployment_is_its_own_files():
    bench = harness.BENCH
    for f in SHUFFLE_FILES:
        assert (bench / f).exists(), f
    spec = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    assert {m["name"] for m in spec["per_layer"]
            if "shuffle_256.locality" in m.get("workloads", [])} >= set(
                NEW_METRICS)
    loaded = harness.load_cell("shuffle_256.locality")
    assert loaded["module"].__name__ == "benchmark.rounds.shuffle"
    # no file of the harness names the deployment: it is found by name
    for path in bench.rglob("*"):
        rel = path.relative_to(bench).as_posix()
        if path.is_file() and "__pycache__" not in rel \
                and rel not in SHUFFLE_FILES and path.suffix in (
                    ".py", ".json"):
            text = path.read_text()
            for word in ("shuffle_256", "rounds.shuffle",
                         "locality_roofline", "import locality"):
                assert word not in text, (rel, word)
