"""The Ray Data deployment (``data_256.spread``) and the ``resync`` mix
(``ns_1k.resync``): small replays through ``run_cell`` and ``judge``,
the per-task SPREAD reference on a case worked by hand, the SPREAD
beat's bytes, the four readers the deployment brings, and that it is its
own files only."""

import json

import numpy as np
import pytest

from benchmark import check, harness
from benchmark.peaks import peaks
from benchmark.reference import spread as ref
from benchmark.roofline import beat_bytes
from benchmark.spread_roofline import spread_beat_bytes

DATA_FILES = {"configs/data_256.json", "traffic/spread.json",
              "rounds/data.py", "reference/spread.py", "spread_roofline.py",
              "metrics/spread_groups_per_beat.py",
              "metrics/per_task_rounds_pct.py",
              "metrics/spread_order_ms.py",
              "metrics/spread_beat_roofline.py", "tests/test_data.py"}
NEW_METRICS = ("spread_groups_per_beat", "per_task_rounds_pct",
               "spread_order_ms", "spread_beat_roofline")


def _small(loaded):
    """The cell at a small size: 64 nodes, rounds of 512 tasks over a
    device floor of 256."""
    loaded["config"].update(nodes=64)
    loaded["traffic"].update(round_tasks=512, pool_batches=2,
                             sample_rounds=3)
    loaded["traffic"]["system_config"] = {"scheduler_device_batch_min": 256}
    return loaded


@pytest.fixture(scope="module")
def record():
    loaded = _small(harness.load_cell("data_256.spread"))
    return harness.run_cell(loaded, 2**31 + 31, 0.5, False, platform=None)


def test_small_replay_correct_and_control_fails(record):
    assert record["rounds"].__class__.__module__ == "benchmark.rounds.data"
    checks = check.judge(record)
    assert set(checks) == set(check.LIMITS)
    assert check.passed(checks, record), checks
    assert not check.passed(check.judge(record, produce=check.control),
                            record)
    engine = record["counters"]["engine"]
    raylet = record["counters"]["raylet"]
    rounds = len(record["round_s"])
    assert rounds > 0 and record["offered"] == 512 * rounds
    # every round took the beat: 4 SPREAD and 4 DEFAULT subgroups
    assert engine["beats"] == rounds
    assert engine["groups"] == 8 * rounds
    assert engine["spread_groups"] == 4 * rounds
    assert raylet["per_task_rounds"] == 0
    assert raylet["spread_host_rounds"] == 0


def test_a_wrong_cursor_is_caught(record):
    # the program's answer with its cursor after the round moved one row
    module = record["module"]

    def moved(record, sample):
        placed, rows, board = module.program_answer(record, sample)
        placed = placed.copy()
        placed[-1] = np.roll(placed[-1][:-1], 1).tolist() + [0]
        return placed, rows, board

    checks = check.judge(record, produce=moved)
    assert checks["placement_cells_off"]["value"] == 2 * len(
        record["samples"])
    assert checks["task_rows_off"]["value"] == 0


def test_spread_tasks_by_hand():
    # rows 0 and 3 fit one task each, row 1 none, row 2 holds too little:
    # from cursor 3 the first two tasks take 3 then 0 (debiting them),
    # the other five go round-robin over rows 0, 1, 3 from cursor 1
    totals = np.array([[400, 0], [400, 0], [100, 0], [400, 0]])
    avail = np.array([[200, 0], [100, 0], [100, 0], [200, 0]])
    rows, after, cursor = ref.spread_tasks(totals, avail, [200, 0], 7, 3)
    assert rows == [3, 0, 1, 3, 0, 1, 3] and cursor == 0
    np.testing.assert_array_equal(after[:, 0], [0, 100, 100, 0])
    # no row can hold the task: -1, the cursor stays
    rows, _, cursor = ref.spread_tasks(totals, avail, [500, 0], 2, 2)
    assert rows == [-1, -1] and cursor == 2


def test_round_carries_avail_and_cursor_between_subgroups():
    totals = np.array([[400, 400], [400, 400], [400, 400]])
    avail = totals.copy()
    reqs = np.array([[200, 0], [100, 100]])
    # class 0 SPREAD, class 1 DEFAULT, class 0 SPREAD again (one
    # subgroup): the DEFAULT subgroup sees the SPREAD debits
    classes, spread = [0, 1, 0, 1], [True, False, True, False]
    placed, rows, after, cursor = ref.schedule(totals, avail, reqs, classes,
                                               spread, 0.5, 2)
    assert rows[0] == 2 and rows[2] == 0 and cursor == 1
    np.testing.assert_array_equal(after[:, 0].sum(), 1200 - 400 - 200)
    # with the carry the DEFAULT tasks see rows 0 and 2 past the spread
    # threshold and both take row 1; the control sees every row empty
    assert placed[1, :3].tolist() == [0, 2, 0]
    ctl = ref.schedule(totals, avail, reqs, classes, spread, 0.5, 2,
                       carry=False)
    assert ctl[0][1, :3].tolist() == [1, 1, 0]


def test_resync_makes_every_beat_a_full_rescore():
    loaded = harness.load_cell("ns_1k.resync")
    assert loaded["traffic"]["dirty_ops_per_round"] == 400
    loaded["config"].update(classes=16, pending_tasks=100_000)
    rec = harness.run_cell(loaded, 2**31 + 37, 0.5, False, platform=None)
    assert check.passed(check.judge(rec), rec)
    engine = rec["counters"]["engine"]
    assert engine["beats"] == len(rec["round_s"]) > 0
    assert engine["full_rescores"] == engine["beats"]
    assert engine["delta_beats"] == 0


def test_spread_beat_bytes_by_hand():
    # data_256: N=256, R=3, C=8, G=8 groups, d=8 dirty rows:
    #   beat_bytes      = 8*256*3 + 256 + 4*8*3 + 4*8 + 8*25
    #                     + 4*8*257 + 4*8*256
    #                   = 6,144 + 256 + 96 + 32 + 200 + 8,224 + 8,192
    #                   = 23,144
    #   flags, cursor   8 + 8
    assert beat_bytes(256, 3, 8, 8, 8) == 23_144
    assert spread_beat_bytes(256, 3, 8, 8, 8) == 23_160


def _read(name, record):
    return harness.per_layer(record, [{"name": name, "unit": "x"}]).get(
        name, {}).get("value")


def _record(engine, raylet=None, spans=None, kernel_s=None):
    return {"config": json.loads(
                (harness.BENCH / "configs" / "data_256.json").read_text()),
            "cell": {"chips": 1}, "round_s": [0.008] * 4,
            "device": {"kind": "TPU v5 lite"},
            "counters": {"engine": engine, "crm": {},
                         "raylet": raylet or {}},
            "trace_summary": {"program_spans": spans or {},
                              "kernel_s": kernel_s}}


def test_readers_by_hand():
    engine = {"beats": 4, "groups": 32, "spread_groups": 16,
              "rows_uploaded": 32}
    spans = {"raylet.spread_order": [0.0004, 0.0006, 0.0005, 0.0009]}
    rec = _record(engine, {"per_task_rounds": 1}, spans,
                  kernel_s=4 * 4e-4)
    assert _read("spread_groups_per_beat", rec) == pytest.approx(4.0)
    assert _read("per_task_rounds_pct", rec) == pytest.approx(25.0)
    assert _read("spread_order_ms", rec) == pytest.approx(0.55)
    # 23,160 bytes at 819 GB/s over 0.4 ms of device time a beat
    want = 100 * 23_160 / peaks("TPU v5 lite")["hbm_bytes_per_s"] / 4e-4
    assert _read("spread_beat_roofline", rec) == pytest.approx(want)


def test_readers_find_nothing_on_a_program_without_them():
    # a program whose rounds take no beat, keeps neither counter and has
    # no rotation span (the per-task path): every reader returns nothing
    for engine in ({}, {"beats": 3, "groups": 24, "full_rescores": 0}):
        rec = _record(engine, {"group_tasks": 4096},
                      {"raylet.group": [0.002]}, kernel_s=1e-3)
        for name in NEW_METRICS:
            assert _read(name, rec) is None, (name, engine)


def test_the_deployment_is_its_own_files():
    bench = harness.BENCH
    for f in DATA_FILES:
        assert (bench / f).exists(), f
    spec = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    assert {m["name"] for m in spec["per_layer"]
            if "data_256.spread" in m.get("workloads", [])} == set(
                NEW_METRICS)
    loaded = harness.load_cell("data_256.spread")
    assert loaded["module"].__name__ == "benchmark.rounds.data"
    # no file of the harness names the deployment: it is found by name
    for path in bench.rglob("*"):
        rel = path.relative_to(bench).as_posix()
        if path.is_file() and "__pycache__" not in rel \
                and rel not in DATA_FILES and path.suffix in (
                    ".py", ".json"):
            text = path.read_text()
            for word in ("data_256", "rounds.data", "spread_roofline",
                         "import spread"):
                assert word not in text, (rel, word)
