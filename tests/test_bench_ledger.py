"""The perf ledger (tools/bench_ledger.py): one canonical row schema
for every bench tool, and a regression gate that fails loudly on an
empty or regressed trajectory (ISSUE 11 acceptance: an injected slow
row fails --ci, an honest row passes)."""

import json
import os

import pytest

from tools import bench_ledger as bl


@pytest.fixture()
def ledger(tmp_path, monkeypatch):
    path = str(tmp_path / "LEDGER.jsonl")
    monkeypatch.setenv("PT_BENCH_LEDGER", path)
    return path


def _row(value, workload="w", backend="cpu", **kw):
    return bl.make_row("test_tool", workload, value, "tokens/sec",
                       backend=backend, metrics={}, **kw)


def test_schema_roundtrip(ledger):
    p = bl.append_row(_row(100.0), path=ledger)
    assert p == ledger
    rows = bl.read_ledger(ledger)
    assert len(rows) == 1
    r = rows[0]
    for k in bl.REQUIRED:
        assert r.get(k) is not None, k
    assert r["schema"] == "bench_ledger/v1"
    assert r["tool"] == "test_tool" and r["value"] == 100.0
    assert len(r["run_id"]) == 12


def test_env_override_and_disable(tmp_path, monkeypatch):
    path = str(tmp_path / "env.jsonl")
    monkeypatch.setenv("PT_BENCH_LEDGER", path)
    assert bl.append("t", "w", 1.0, "u") == path
    assert os.path.exists(path)
    monkeypatch.setenv("PT_BENCH_LEDGER", "0")
    assert bl.append("t", "w", 1.0, "u") is None


def test_malformed_row_rejected(ledger):
    row = _row(1.0)
    del row["git_rev"]
    with pytest.raises(ValueError, match="git_rev"):
        bl.append_row(row, path=ledger)
    row = _row(1.0)
    row["schema"] = "bench_ledger/v0"
    with pytest.raises(ValueError, match="schema"):
        bl.append_row(row, path=ledger)


def test_reader_skips_garbage_lines(ledger):
    bl.append_row(_row(1.0), path=ledger)
    with open(ledger, "a") as f:
        f.write("not json\n")
        f.write(json.dumps({"schema": "other"}) + "\n")
    bl.append_row(_row(2.0), path=ledger)
    assert [r["value"] for r in bl.read_ledger(ledger)] == [1.0, 2.0]


def test_ci_empty_trajectory_fails_loudly(ledger):
    assert bl.ci_gate(path=ledger) == 2          # no file at all
    open(ledger, "w").close()
    assert bl.ci_gate(path=ledger) == 2          # empty file
    with open(ledger, "w") as f:
        f.write("garbage\n")
    assert bl.ci_gate(path=ledger) == 2          # unreadable rows only


def test_ci_honest_row_passes_injected_slow_row_fails(ledger):
    # an honest trajectory: stable values within noise
    for v in (100.0, 104.0, 98.0, 101.0):
        bl.append_row(_row(v), path=ledger)
    assert bl.ci_gate(path=ledger) == 0

    # injected regression: the newest row fell off a cliff
    bl.append_row(_row(30.0), path=ledger)
    assert bl.ci_gate(path=ledger) == 3

    # an honest recovery row passes again (baseline = median of prior)
    bl.append_row(_row(99.0), path=ledger)
    assert bl.ci_gate(path=ledger) == 0


def test_ci_single_row_series_is_new_not_fail(ledger):
    bl.append_row(_row(42.0), path=ledger)
    assert bl.ci_gate(path=ledger) == 0
    v = bl.compare(bl.read_ledger(ledger))
    assert v[0]["status"] == "new"


def test_tolerance_tight_on_hardware_wide_on_cpu(ledger):
    # 20% drop: inside the CPU tolerance, outside the TPU one
    for v in (100.0, 100.0, 80.0):
        bl.append_row(_row(v, workload="cpu_w", backend="cpu"),
                      path=ledger)
    for v in (100.0, 100.0, 80.0):
        bl.append_row(_row(v, workload="hw_w", backend="TPU v5 lite"),
                      path=ledger)
    verdicts = {v["workload"]: v["status"]
                for v in bl.compare(bl.read_ledger(ledger))}
    assert verdicts["cpu_w"] == "ok"
    assert verdicts["hw_w"] == "regressed"
    assert bl.ci_gate(path=ledger) == 3


def test_direction_lower_is_better(ledger):
    for v in (10.0, 10.0):
        bl.append_row(_row(v, workload="lat", direction="lower"),
                      path=ledger)
    # latency doubled: with direction=lower that IS the regression
    bl.append_row(_row(25.0, workload="lat", direction="lower"),
                  path=ledger)
    assert bl.ci_gate(path=ledger) == 3


def test_series_keyed_by_host(ledger, monkeypatch):
    # a slower machine's rows start their OWN trajectory: committed
    # fast-host baselines must not fail a contributor's CI run
    monkeypatch.setenv("PT_BENCH_HOST", "fast-host")
    for v in (1000.0, 1000.0):
        bl.append_row(_row(v), path=ledger)
    monkeypatch.setenv("PT_BENCH_HOST", "slow-host")
    bl.append_row(_row(300.0), path=ledger)   # 3.3x slower machine
    assert bl.ci_gate(path=ledger) == 0
    verdicts = {(v["host"]): v["status"]
                for v in bl.compare(bl.read_ledger(ledger))}
    assert verdicts["fast-host"] == "ok"
    assert verdicts["slow-host"] == "new"
    # same slow host regressing against ITS OWN baseline still fails
    bl.append_row(_row(300.0), path=ledger)
    bl.append_row(_row(50.0), path=ledger)
    assert bl.ci_gate(path=ledger) == 3


def test_series_keyed_by_workload_and_backend(ledger):
    # the same workload on another backend is its own series: a CPU
    # smoke number must never read as a TPU regression
    bl.append_row(_row(100000.0, backend="TPU v5 lite"), path=ledger)
    bl.append_row(_row(400.0, backend="cpu"), path=ledger)
    assert bl.ci_gate(path=ledger) == 0


def test_emitters_share_the_schema():
    """The repo trajectory (BENCH_LEDGER.jsonl) carries rows from the
    bench tools in the one schema — the acceptance pin. Skipped
    only if a fresh checkout hasn't run the bench steps yet."""
    rows = bl.read_ledger(bl.DEFAULT_PATH)
    if not rows:
        pytest.skip("no repo ledger yet (bench tools not run)")
    tools = {r["tool"] for r in rows}
    assert {"llm_bench", "bench"} <= tools, tools
    for r in rows:
        assert r["schema"] == "bench_ledger/v1"
