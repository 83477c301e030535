"""``correct`` must come out false when the timed path is broken.

Each test drives a whole run of a cell, short of the harness's look for
a chip, on the CPU at a size a test can hold, with a fault planted in the
program underneath, and sees the comparison fail.  The faults are those
the contract names that a cell can have: a step that returns its state
unchanged, half of the batch left out, the exchange between chips left
out (the sharded cell), an answer altered where it is produced.  The
last tests put the control (the reference with the sequential class
order broken) in the program's place and see it fail too, and see sound
runs pass.
"""

import numpy as np
import pytest

from benchmark import check, harness

SMALL = {
    "ns_1k.churn": {"nodes": 512, "classes": 16, "pending_tasks": 100_000},
    "h256.arrivals": {"pool_batches": 2},
    "envelope_2k.sharded4": {"nodes": 512, "classes": 16,
                             "pending_tasks": 100_000},
}
ENGINE_CELLS = ["ns_1k.churn", "h256.arrivals", "envelope_2k.sharded4"]
ROW_CELLS = ["h256.arrivals"]
DELTA_CELLS = ["ns_1k.churn", "envelope_2k.sharded4"]


def _run(workload, seed=20260001, seconds=0.6):
    loaded = harness.load_cell(workload)
    for k, v in SMALL[workload].items():
        (loaded["traffic"] if k in loaded["traffic"]
         else loaded["config"])[k] = v
    record = harness.run_cell(loaded, seed, seconds, False, platform=None)
    return record, check.judge(record)


def _failed(checks, record):
    return not check.passed(checks, record)


@pytest.mark.parametrize("workload", list(SMALL))
def test_sound_runs_pass(workload):
    record, checks = _run(workload)
    assert check.passed(checks, record), checks
    # the program's counters by source, each round one beat (so that
    # ``kernel_ms`` per round is per beat)
    counts = record["counters"]
    assert counts["engine"]["beats"] == len(record["round_s"]) > 0
    assert counts["crm"]["journal_scanned"] > 0
    assert {"group_tasks", "group_probed"} <= set(counts["raylet"])
    assert (counts["raylet"]["group_tasks"] > 0) == (
        workload == "h256.arrivals")
    if workload == "envelope_2k.sharded4":
        assert record["engine"] == {"type": "ShardedDeltaScheduler",
                                    "shards": 4}


@pytest.mark.parametrize("workload", ENGINE_CELLS)
def test_engine_state_left_unchanged(workload, monkeypatch):
    from ray_tpu.scheduling.policy import DeltaScheduler
    from ray_tpu.scheduling.sharded_delta import ShardedDeltaScheduler
    for cls in (DeltaScheduler, ShardedDeltaScheduler):
        monkeypatch.setattr(cls, "_delta_sync", lambda self, *a, **kw: None)
    record, checks = _run(workload)
    assert record["counters"]["engine"]["delta_beats"] > 0
    assert _failed(checks, record), checks


@pytest.mark.parametrize("workload", DELTA_CELLS)
def test_half_the_classes_left_out_of_the_beat(workload, monkeypatch):
    from ray_tpu.scheduling.policy import DeltaScheduler
    real = DeltaScheduler.beat

    def half(self, reqs, counts, *a, **kw):
        counts = np.array(counts)
        counts[len(counts) // 2:] = 0
        return real(self, reqs, counts, *a, **kw)
    monkeypatch.setattr(DeltaScheduler, "beat", half)
    record, checks = _run(workload)
    assert _failed(checks, record), checks


@pytest.mark.parametrize("workload", ROW_CELLS)
def test_half_the_batch_left_out(workload, monkeypatch):
    from ray_tpu.runtime.raylet import Raylet
    real = Raylet._schedule_rows_soft

    def half(self, batch):
        rows = real(self, batch[:len(batch) // 2])
        return rows + [-1] * (len(batch) - len(rows))
    monkeypatch.setattr(Raylet, "_schedule_rows_soft", half)
    record, checks = _run(workload)
    assert _failed(checks, record), checks


def test_exchange_between_chips_left_out(monkeypatch):
    from ray_tpu.ops import shard_reduce
    monkeypatch.setattr(shard_reduce, "_psum2", lambda x: x)
    monkeypatch.setattr(shard_reduce, "_pmin2", lambda x: x)
    # programs traced by an earlier test would keep the exchange
    monkeypatch.setattr(shard_reduce.plane_for, "__defaults__", ("auto", {}))
    record, checks = _run("envelope_2k.sharded4")
    assert record["engine"]["shards"] == 4
    assert _failed(checks, record), checks


@pytest.mark.parametrize("workload", ENGINE_CELLS)
def test_one_count_altered_in_the_beat(workload, monkeypatch):
    from ray_tpu.scheduling.policy import DeltaScheduler
    real = DeltaScheduler.beat

    def altered(self, *a, **kw):
        out = real(self, *a, **kw).copy()
        g, n = np.unravel_index(np.argmax(out[:, :-1]), out[:, :-1].shape)
        out[g, n] -= 1
        out[g, (n + 1) % (out.shape[1] - 1)] += 1
        return out
    monkeypatch.setattr(DeltaScheduler, "beat", altered)
    record, checks = _run(workload)
    assert _failed(checks, record), checks


def test_crm_churn_booked_wrong(monkeypatch):
    """A refund the CRM books twice: the reference, placing against the
    generator's own record, sees the extra capacity the program uses."""
    from ray_tpu.scheduling.cluster_resources import ClusterResourceManager
    real = ClusterResourceManager.add_back

    def twice(self, row, req):
        real(self, row, req)
        real(self, row, req)
    monkeypatch.setattr(ClusterResourceManager, "add_back", twice)
    record, checks = _run("ns_1k.churn")
    assert _failed(checks, record), checks


def test_generator_record_matches_the_crm():
    """Sound runs: the generator's record is the CRM's state, column by
    column, after set-up and rounds with churn between them (a round
    plans; it books nothing in the CRM)."""
    from benchmark.generator import Churn, Deployment
    from benchmark.rounds.hybrid import Rounds
    from ray_tpu.common.config import Config
    loaded = harness.load_cell("h256.arrivals")
    loaded["traffic"]["pool_batches"] = 1
    Config.reset(loaded["config"]["system_config"])
    dep = Deployment(loaded["config"], 20260002)
    try:
        rounds = Rounds(dep, loaded["config"], loaded["traffic"])
        churn = Churn(dep, {"CPU": 1}, 64, 20260002)
        for i in range(6):
            churn.step()
            dep.raylet._schedule_rows(rounds.at(i)[1][0])
        st = dep.crm.snapshot()
        cols = [dep.crm._col(k) for k in dep.node_names]
        n = len(dep.totals)
        assert (np.asarray(st.totals)[:n, cols] == dep.totals).all()
        assert (np.asarray(st.avail)[:n, cols] == dep.avail).all()
        assert np.asarray(st.node_mask)[:n].all()
        assert not dep.crm.suspect_mask().any()
        assert (dep.class_vecs[:, cols] == dep.ref_vecs).all()
    finally:
        dep.stop()


@pytest.mark.parametrize("workload", list(SMALL))
def test_control_fails(workload):
    record, checks = _run(workload)
    assert check.passed(checks, record), checks
    control = check.judge(record, produce=check.control)
    assert _failed(control, record), control
    assert control["placement_cells_off"]["value"] > 0, control
