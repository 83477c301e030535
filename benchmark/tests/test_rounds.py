"""The round modules: a configuration's ``rounds`` key picks
``rounds/<name>.py``, and a new deployment runs through the harness and
the check as new files only.

``hybrid``'s answers and tallies are pinned to what the harness gave
before its code moved into ``rounds/hybrid.py``: the digests below were
computed by the same replay on that code.
"""

import hashlib
import json
import os
import shutil
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from benchmark import harness
from benchmark.generator import Churn, Deployment

SMALL = {"ns_1k.churn": {"nodes": 256, "classes": 16,
                         "pending_tasks": 100_000},
         "h256.arrivals": {"pool_batches": 2}}
BEFORE_THE_MOVE = {
    "ns_1k.churn": ([[100_000, 100_000]] * 6, "637406317e65c0c28a39c2ddce"
                    "da7c1198e0042bd3315ad38188006a12253270"),
    "h256.arrivals": ([[4096, 4096]] * 6, "d64a0a62193af881ac35850fbe5f"
                      "e04e9b38855ddec9f757879491b93614a2dd"),
}


def _replay(workload, seed, n):
    """``n`` rounds of the cell at a small size with churn between them,
    as the window drives them: each round's tally, and a digest of the
    reference's and the program's answers."""
    from ray_tpu.common.config import Config

    loaded = harness.load_cell(workload)
    config, traffic, module = (loaded[k] for k in
                               ("config", "traffic", "module"))
    for k, v in SMALL[workload].items():
        (traffic if k in traffic else config)[k] = v
    Config.reset({**config["system_config"], **traffic["system_config"]})
    dep = Deployment(config, seed)
    tap = harness.BoardTap()
    try:
        rounds = module.Rounds(dep, config, traffic)
        churn = Churn(dep, traffic["dirty_request"],
                      traffic["dirty_ops_per_round"], seed)
        record = {"config": config, "totals": dep.totals,
                  "ref_vecs": dep.ref_vecs, "class_keys": dep.class_keys,
                  "rounds": rounds, "module": module}
        digest = hashlib.sha256()
        tallies = []
        for i in range(n):
            churn.step()
            j, payload = rounds.at(i)
            avail = dep.avail.copy()
            published = tap.count
            out = rounds.call(payload)
            tallies.append(list(rounds.tally(payload, out)))
            sample = {"index": j, "avail": avail, "out": out,
                      "board": tap.rows if tap.count > published else None}
            for placed, rows, budgets in (
                    module.reference_answer(record, sample),
                    module.program_answer(record, sample)):
                digest.update(np.asarray(placed, np.int64).tobytes())
                if rows is not None:
                    digest.update(np.asarray(rows, np.int64).tobytes())
                for k in sorted(budgets or {}):
                    digest.update(k.encode())
                    digest.update(np.asarray(budgets[k], np.int64).tobytes())
    finally:
        tap.close()
        dep.stop()
    return tallies, digest.hexdigest()


@pytest.mark.parametrize("workload", list(BEFORE_THE_MOVE))
def test_hybrid_answers_as_before_the_move(workload):
    assert _replay(workload, 2**31 + 11, 6) == BEFORE_THE_MOVE[workload]


def test_no_rounds_key_means_hybrid():
    from benchmark.rounds import hybrid
    loaded = harness.load_cell("h256.arrivals")
    assert "rounds" not in loaded["config"]
    assert loaded["module"] is hybrid


# A deployment that exists only in the copy: the whole backlog as class
# counts, but each round offers one of ``pool_batches`` drawn count
# vectors, checked by a reference of its own.
TOY_ROUNDS = '''
import numpy as np

from ..reference import toy as ref
from . import hybrid


class Rounds(hybrid.Rounds):
    def __init__(self, dep, config, traffic):
        self.dep, self.entry = dep, "delta"
        g = len(dep.class_cu)
        self.pool = [dep.rng.multinomial(traffic["round_tasks"],
                                         np.full(g, 1 / g)).astype(np.int32)
                     for _ in range(traffic["pool_batches"])]

    def at(self, i):
        return i % len(self.pool), self.pool[i % len(self.pool)]


def reference_answer(record, sample, carry=True):
    counts = record["rounds"].pool[sample["index"]]
    return ref.answer(record, sample["avail"], counts, carry)


def program_answer(record, sample):
    return np.asarray(sample["out"]), None, sample["board"]
'''
TOY_REFERENCE = '''
import numpy as np

from . import oracle


def answer(record, avail, counts, carry):
    totals, vecs = record["totals"], record["ref_vecs"]
    thr = record["config"]["system_config"]["scheduler_spread_threshold"]
    mask = np.ones(len(totals), bool)
    placed, after = oracle.schedule_grouped(totals, avail, mask, vecs,
                                            counts, thr, carry=carry)
    budgets = oracle.compute_budgets(totals, after, vecs, mask)
    return placed, None, dict(zip(record["class_keys"], budgets))
'''
TOY_RUN = '''
import json, sys
sys.path.insert(0, sys.argv[1])
from benchmark import check, harness
loaded = harness.load_cell("toy_64.pool")
record = harness.run_cell(loaded, 2**31 + 5, 0.5, False, platform=None)
print(json.dumps({"module": loaded["module"].__file__,
                  "per_layer": [m["name"] for m in loaded["per_layer"]],
                  "rounds": len(record["round_s"]),
                  "offered": record["offered"],
                  "correct": check.passed(check.judge(record), record),
                  "control": check.passed(check.judge(
                      record, produce=check.control), record)}))
'''


def _tree(root):
    """{relative path: bytes} of every file under ``root``."""
    out = {}
    for d, _dirs, files in os.walk(root):
        for f in files:
            if "__pycache__" not in d:
                p = os.path.join(d, f)
                out[os.path.relpath(p, root)] = open(p, "rb").read()
    return out


def test_new_deployment_as_new_files(tmp_path):
    shutil.copy(harness.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(harness.BENCH, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = _tree(tmp_path / "benchmark")
    bench = tmp_path / "benchmark"
    config = json.loads((bench / "configs" / "ns_1k.json").read_text())
    config.update(name="toy_64", source="a test", nodes=64, classes=8,
                  rounds="toy")
    (bench / "configs" / "toy_64.json").write_text(json.dumps(config))
    (bench / "traffic" / "pool.json").write_text(json.dumps({
        "name": "pool", "round_tasks": 5000, "pool_batches": 3,
        "dirty_ops_per_round": 4, "dirty_request": {"CPU": 1},
        "sample_rounds": 3, "system_config": {}}))
    (bench / "rounds" / "toy.py").write_text(textwrap.dedent(TOY_ROUNDS))
    (bench / "reference" / "toy.py").write_text(
        textwrap.dedent(TOY_REFERENCE))
    spec = json.loads((tmp_path / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "toy_64", "source": "a test",
                            "file": "benchmark/configs/toy_64.json",
                            "reduced": [], "why": "a test"})
    spec["workloads"].append({"name": "toy_64.pool", "config": "toy_64",
                              "traffic": "pool", "chips": 1,
                              "why": "a test"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))

    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "JAX_ENABLE_COMPILATION_CACHE": "false",
           "PYTHONPATH": str(harness.ROOT)}
    p = subprocess.run([sys.executable, "-c", TOY_RUN, str(tmp_path)],
                       cwd=tmp_path, env=env, capture_output=True,
                       text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    got = json.loads(p.stdout.strip().splitlines()[-1])
    assert got["module"] == str(bench / "rounds" / "toy.py")
    assert got["rounds"] > 0 and got["offered"] == 5000 * got["rounds"]
    assert got["correct"] and not got["control"]
    # the metrics with no ``workloads`` list reach the new cell unasked,
    # and the copy passes the spec's checks with it
    assert set(got["per_layer"]) == {
        "raylet_host_ms", "kernel_ms", "device_idle_pct",
        "compiles_in_window", "idle_explained_pct"}
    p = subprocess.run([sys.executable, "-m", "pytest", "-v",
                        "-p", "no:cacheprovider",
                        "benchmark/tests/test_spec.py"],
                       cwd=tmp_path, env=env, capture_output=True,
                       text=True, timeout=600)
    assert p.returncode == 0, p.stdout[-3000:]
    assert "test_cell_resolves[toy_64.pool] PASSED" in p.stdout
    # no file the copy had was edited: the deployment is new files only
    after = _tree(bench)
    assert {k: after[k] for k in before} == before
    assert set(after) - set(before) == {
        "configs/toy_64.json", "traffic/pool.json", "rounds/toy.py",
        "reference/toy.py"}
