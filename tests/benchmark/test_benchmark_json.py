"""BENCHMARK.json against the contract's limits, and against the files the
harness finds by name."""
import json
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
CELLS = [w["name"] for w in BENCH["workloads"]]
METRICS = BENCH["end_to_end"] + BENCH["per_layer"]


def test_top_level_keys_and_limits():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51
    cells = len(BENCH["workloads"])
    assert 1 <= cells <= 24
    budget = (2 + 14 * 24) * (BENCH["run_seconds"] + 60) + 24 * 180 + 1200
    assert budget <= 43200
    four = sum(w["chips"] == 4 for w in BENCH["workloads"])
    assert four <= max(1, cells // 4)
    assert len(json.dumps(BENCH)) < 64 * 1024


@pytest.mark.parametrize("entry", BENCH["configs"] + BENCH["workloads"]
                         + METRICS, ids=lambda e: e["name"])
def test_names_units_and_lines(entry):
    assert NAME.match(entry["name"])
    if "unit" in entry:
        assert UNIT.match(entry["unit"])
        assert entry["better"] in ("lower", "higher")
        assert entry["source"] in ("device_trace", "program_span",
                                   "program_counter", "host_clock")
    for key in ("why", "layer", "source"):
        if key in entry:
            assert 1 <= len(entry[key]) <= 200
            assert "\n" not in entry[key] and "\t" not in entry[key]


def test_names_are_unique():
    for group in (BENCH["configs"], BENCH["workloads"], METRICS):
        names = [e["name"] for e in group]
        assert len(names) == len(set(names))
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(pairs) == len(set(pairs))


def test_end_to_end_bounds_and_setup():
    by = {m["name"]: m for m in BENCH["end_to_end"]}
    assert "setup_s" in by and by["setup_s"]["bound"] <= 0.1
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}


@pytest.mark.parametrize("cell", BENCH["workloads"], ids=lambda w: w["name"])
def test_each_cell_reports_setup_another_metric_and_a_layer(cell):
    def reported(m):
        return cell["name"] in m.get("workloads", CELLS)
    e2e = [m["name"] for m in BENCH["end_to_end"] if reported(m)]
    assert "setup_s" in e2e and len(e2e) >= 2
    assert any(reported(m) for m in BENCH["per_layer"])
    assert cell["chips"] in (1, 4)
    assert cell["config"] in [c["name"] for c in BENCH["configs"]]


@pytest.mark.parametrize("metric", BENCH["per_layer"],
                         ids=lambda m: m["name"])
def test_moves_target_is_reported_where_the_layer_metric_is(metric):
    assert set(metric) <= {"name", "unit", "better", "source", "layer",
                           "moves", "workloads"}
    target = next(m for m in BENCH["end_to_end"]
                  if m["name"] == metric["moves"])
    for cell in metric.get("workloads", CELLS):
        assert cell in CELLS
        assert cell in target.get("workloads", CELLS)
    reader = os.path.join(ROOT, "benchmark", "layer_metrics",
                          metric["name"] + ".py")
    assert os.path.exists(reader)


def test_files_found_by_name_exist_and_configs_state_their_cuts():
    under = tuple(p + "/" for p in BENCH["paths"])
    for cfg in BENCH["configs"]:
        assert cfg["file"].startswith(under)
        data = json.load(open(os.path.join(ROOT, cfg["file"])))
        assert data["reduced"] == cfg["reduced"]
        assert len(cfg["reduced"]) <= 16
        for key in cfg["reduced"]:
            assert NAME.match(key)
            assert not re.search(r"(hidden|intermediate|_dim$|_rank$|head_dim|"
                                 r"ffn)", key)
        assert os.path.exists(os.path.join(
            ROOT, "benchmark", "systems", data["system"] + ".py"))
    for cell in BENCH["workloads"]:
        mix = json.load(open(os.path.join(
            ROOT, "benchmark", "workloads", cell["traffic"] + ".json")))
        assert os.path.exists(os.path.join(
            ROOT, "benchmark", "traffic", mix["kind"] + ".py"))
        assert os.path.exists(os.path.join(
            ROOT, "benchmark", "checks", cell["name"] + ".json"))
    assert all(not w.startswith("/") and ".." not in w
               for w in BENCH["command"])
