"""``BENCHMARK.json`` against the contract's shape, and every name in it
resolving to its file: configs, their round modules, traffic mixes and
metric readers."""

import json
import re

import pytest

from benchmark import harness

SPEC = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
KEYS = {"configs": {"name", "source", "file", "reduced", "why"},
        "workloads": {"name", "config", "traffic", "chips", "why"},
        "end_to_end": {"name", "unit", "better", "bound", "source"},
        "per_layer": {"name", "unit", "better", "source", "layer",
                      "moves"}}


def test_top_level_and_entries():
    assert set(SPEC) == {"command", "paths", "run_seconds", *KEYS}
    assert SPEC["paths"] == ["benchmark"]
    assert not any(w.startswith("/") or ".." in w for w in SPEC["command"])
    cells = len(SPEC["workloads"])
    assert sum(w["chips"] == 4 for w in SPEC["workloads"]) <= max(
        1, cells // 2)
    # a full check of 24 cells fits its 43,200 seconds
    runs = 2 + 14 * 24
    assert runs * (SPEC["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200
    for section, keys in KEYS.items():
        names = [e["name"] for e in SPEC[section]]
        assert len(names) == len(set(names))
        for e in SPEC[section]:
            assert keys <= set(e) <= keys | {"workloads"}, e
            assert NAME.match(e["name"]), e["name"]
            for k in ("why", "layer", "source"):
                if k in e:
                    assert 1 <= len(e[k]) <= 200 and "\n" not in e[k], e
            if "unit" in e:
                assert UNIT.match(e["unit"]) and e["better"] in (
                    "lower", "higher")
                assert e["source"] in ("device_trace", "program_span",
                                       "program_counter", "host_clock")


def test_bounds_and_sources():
    for m in SPEC["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25 and m["source"] == "host_clock"
    assert {m["name"] for m in SPEC["end_to_end"]} >= {"setup_s"}


@pytest.mark.parametrize("cell", SPEC["workloads"], ids=lambda c: c["name"])
def test_cell_resolves(cell):
    loaded = harness.load_cell(cell["name"])
    assert loaded["config"]["name"] == cell["config"]
    assert loaded["traffic"]["name"] == cell["traffic"]
    assert loaded["config"]["reduced"] == {
        c["name"]: c for c in SPEC["configs"]}[cell["config"]]["reduced"]
    rounds = loaded["config"].get("rounds", "hybrid")
    assert (harness.BENCH / "rounds" / f"{rounds}.py").exists()
    assert cell["chips"] in (1, 4)
    e2e = {m["name"] for m in loaded["end_to_end"]}
    assert e2e == {m["name"] for m in SPEC["end_to_end"]
                   if cell["name"] in m.get("workloads", [cell["name"]])}
    # set-up, one more end-to-end metric and one per-layer metric
    assert "setup_s" in e2e and len(e2e) >= 2 and loaded["per_layer"]
    for m in loaded["per_layer"]:
        assert (harness.BENCH / "metrics" / f"{m['name']}.py").exists()
        assert m["moves"] in {e["name"] for e in loaded["end_to_end"]}


def test_config_entries_match_files():
    files = [c["file"] for c in SPEC["configs"]]
    assert len(files) == len(set(files))
    for c in SPEC["configs"]:
        body = json.loads((harness.ROOT / c["file"]).read_text())
        assert body["name"] == c["name"] and body["source"] == c["source"]
        assert body["reduced"] == c["reduced"]
