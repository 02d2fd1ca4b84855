"""``paddle_tpu/profiler/scopes.py`` (the wire-format decoder of a trace's
operation metadata) on the traces recorded on one v5e chip beside this file:
``probe.xplane.pb`` (``data/record_trace.py``, no named scope) and
``probe_scopes.xplane.pb`` (``data/record_scope_trace.py``: two named scopes
inside a scan and a named pallas kernel)."""
import importlib.util
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
PROBE = os.path.join(HERE, "data", "probe.xplane.pb")
SCOPED = os.path.join(HERE, "data", "probe_scopes.xplane.pb")


@pytest.fixture(scope="module")
def scopes():
    # by file: the decoder needs neither the package nor jax
    path = os.path.join(ROOT, "paddle_tpu", "profiler", "scopes.py")
    spec = importlib.util.spec_from_file_location("scopes_under_test", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_scope_of(scopes):
    for tf_op, want in [
            ("jit(mixed_fn)/while/body/kv_gather/gather:", "kv_gather"),
            ("jit(mixed_fn)/while/body/cond/branch_1_fun/kv_gather/"
             "jit(_take)/gather:", "kv_gather"),
            ("jit(step)/jvp(attn)/flash_attention_fwd/pallas_call:",
             "attn/flash_attention_fwd"),
            ("jit(mixed_fn)/while/body/attn_scores/bqhd,blhd->bhql/"
             "dot_general:", "attn_scores/bqhd,blhd->bhql"),
            ("jit(mixed_fn)/while/body/closed_call/attn/dot_general:",
             "attn"),
            ("jit(step)/jvp(mlp)/dot_general:", "mlp"),
            ("jit(step)/transpose(jvp(attn))/transpose:", "attn"),
            ("jit(step)/jvp(loss)/fused_xent/while/body/dot_general:",
             "loss/fused_xent"),
            ("jit(step)/optimizer/mul:", "optimizer"),
            ("jit(probe_scan)/while/body/closed_call/dot_general:",
             "(no scope)"),
            ("jit(probe_matmul)/dot_general:", "(no scope)"),
            ("", "(no scope)"), (None, "(no scope)")]:
        assert scopes.scope_of(tf_op) == want, tf_op
    assert scopes.op_path("jit(f)/a/dot_general:") == "jit(f)/a/dot_general"


def test_decoder_agrees_with_profile_data_on_the_probe(scopes):
    import jax
    planes = {p.name: p for p in scopes.read_planes(PROBE)}
    pd = jax.profiler.ProfileData.from_file(PROBE)
    for plane in pd.planes:
        mine = planes[plane.name]
        for line in plane.lines:
            events = list(line.events)
            got = mine.lines[line.name]
            assert len(got) == len(events)
            for (a, b, mid), e in zip(got, events):
                assert mine.event_names[mid] == e.name
                assert a == pytest.approx(e.start_ns * 1e-9, abs=2e-9)
                assert b - a == pytest.approx(e.duration_ns * 1e-9, abs=2e-9)


def test_probe_operations_are_found_by_their_full_path(scopes):
    full = scopes.by_scope(PROBE, key=scopes.op_path)
    assert {k: v["executions"] for k, v in full.items()} == {
        "probe_matmul": 5, "probe_scan": 5}
    path = "jit(probe_scan)/while/body/closed_call/dot_general"
    rows = full["probe_scan"]["scopes"]
    assert max(rows, key=rows.get) == path
    # four ticks of the matmul of probe_matmul
    ratio = rows[path] / full["probe_matmul"]["scopes"][
        "jit(probe_matmul)/dot_general"]
    assert 3.0 < ratio < 4.5
    # nothing in this probe was traced under a named scope; an operation
    # that carries no path at all is listed under XLA's name for it
    rows = scopes.by_scope(PROBE)["probe_scan"]["scopes"]
    assert all(r.startswith("(no scope)") for r in rows)
    assert {"(no scope)", "(no scope) copy-done", "(no scope) while"} <= set(
        rows)
    for prog in full.values():
        assert sum(prog["scopes"].values()) <= prog["seconds"] * 1.05


def test_named_scopes_and_the_named_kernel_of_a_real_tpu_trace(scopes):
    table = scopes.by_scope(SCOPED)
    assert set(table) == {"probe_scoped"}
    prog = table["probe_scoped"]
    assert prog["executions"] == 5
    rows = prog["scopes"]
    named = ("probe_gather", "probe_scores", "probe_tail/probe_kernel")
    assert set(named) <= set(rows)
    # the matmul dominates; every scope took device time; the jitted
    # helpers of jax.numpy (jit(_take)) do not split a scope
    assert max(rows, key=rows.get) == "probe_scores"
    assert all(rows[s] > 0 for s in named)
    assert all(r in named or r.startswith("(no scope)") for r in rows)
    # the pallas kernel is found by the name it was given
    assert prog["kernels"] == {
        "probe_kernel": rows["probe_tail/probe_kernel"]}
    assert sum(rows.values()) <= prog["seconds"] * 1.05


def test_the_tool_prints_the_table_without_jax():
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "trace_scopes.py"),
         PROBE, "--full"], capture_output=True, text=True, timeout=120,
        env={k: v for k, v in os.environ.items() if k != "PYTHONPATH"})
    assert out.returncode == 0, out.stderr
    assert "probe_scan: 5 executions" in out.stdout
    assert "jit(probe_scan)/while/body/closed_call/dot_general" in out.stdout
