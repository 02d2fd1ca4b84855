"""Spans that follow the profiler session (ISSUE 24): ``tracing.active()`` is
``enabled()`` or a running JAX profiler session; a leaf ``phase`` lands in
the span table AND in the host plane of the profiler's trace; containers stay
in the table only; a tree rooted while active is kept to its end; the engine
thread's phases tile its loop without nesting; ``RecordEvent`` delegates."""
import glob
import os
import subprocess
import sys
import time

import jax
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import profiler
from paddle_tpu.observability import tracing

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ENGINE_PHASES = ("llm.loop.", "llm.issue.", "llm.drain.")


@pytest.fixture(autouse=True)
def _off_and_clean():
    tracing.disable()
    tracing.clear()
    yield
    tracing.disable()
    tracing.clear()


def _host_events(trace_dir):
    path = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                            recursive=True))[-1]
    pd = jax.profiler.ProfileData.from_file(path)
    return [(e.name, e.start_ns, e.duration_ns)
            for plane in pd.planes if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events]


def _names(spans):
    return [s["name"] for s in spans]


def test_phase_is_in_the_table_and_the_host_plane_only_during_a_session(
        tmp_path):
    assert not tracing.active()
    with tracing.phase("probe.before"):
        pass
    jax.profiler.start_trace(str(tmp_path))
    try:
        assert tracing.active() and not tracing.enabled()
        with tracing.span("probe.container"):
            with tracing.phase("probe.leaf", {"k": 1}) as leaf:
                time.sleep(0.002)
                assert tracing.current_span().name == "probe.container"
        child = tracing.start_span("probe.child", parent=leaf)
    finally:
        jax.profiler.stop_trace()
    assert not tracing.active()
    with tracing.phase("probe.after"):
        pass
    child.end()         # rooted while active: kept to its end
    assert tracing.start_span("probe.orphan") is tracing.NOOP_SPAN
    table = {s["name"]: s for s in tracing.finished_spans()}
    assert set(table) == {"probe.container", "probe.leaf", "probe.child"}
    assert table["probe.leaf"]["parent_id"] == \
        table["probe.container"]["span_id"]
    assert table["probe.leaf"]["attrs"] == {"k": 1}
    host = {name: dur for name, _, dur in _host_events(str(tmp_path))}
    assert host["probe.leaf"] >= 2e6            # ns, on the profiler's clock
    assert "probe.container" not in host        # containers: table only
    assert not {"probe.before", "probe.after"} & set(host)


def test_enable_still_means_always_and_costs_no_annotation(tmp_path):
    tracing.enable()
    with tracing.phase("always.leaf"):
        pass
    assert _names(tracing.finished_spans()) == ["always.leaf"]


def test_tracing_never_imports_jax():
    code = (
        "import importlib.util, sys\n"
        "spec = importlib.util.spec_from_file_location('tr', sys.argv[1])\n"
        "tr = importlib.util.module_from_spec(spec)\n"
        "spec.loader.exec_module(tr)\n"
        "assert not tr.active()\n"
        "with tr.phase('off'): pass\n"
        "tr.enable()\n"
        "with tr.span('outer'):\n"
        "    with tr.phase('leaf'): pass\n"
        "assert [s['name'] for s in tr.finished_spans()] == "
        "['leaf', 'outer']\n"
        "assert not any(m == 'jax' or m.startswith('jax.') "
        "for m in sys.modules), 'jax was imported'\n")
    path = os.path.join(REPO, "paddle_tpu", "observability", "tracing.py")
    out = subprocess.run([sys.executable, "-c", code, path],
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr


def test_record_event_delegates_to_the_span_table(tmp_path):
    with profiler.RecordEvent("rec.off"):
        pass
    assert tracing.finished_spans() == []
    prof = profiler.Profiler(log_dir=str(tmp_path))
    prof.start()
    ev = profiler.RecordEvent("rec.on")
    ev.begin()
    ev.end()
    prof.stop()
    assert _names(tracing.finished_spans()) == ["rec.on"]
    assert "rec.on" in prof.summary()
    assert "rec.on" in {n for n, _, _ in _host_events(str(tmp_path))}


def _tiny_gpt():
    from paddle_tpu.models.gpt import GPTForCausalLM, gpt_config
    pt.seed(0)
    return GPTForCausalLM(gpt_config(
        "gpt2-small", num_layers=2, hidden_size=64, num_heads=4,
        vocab_size=97, max_position_embeddings=96, hidden_dropout=0.0,
        attention_dropout=0.0))


def test_engine_phases_tile_the_loop_and_requests_keep_their_tree(tmp_path):
    from paddle_tpu.inference.llm import LLMEngine
    rng = np.random.RandomState(0)
    prompts = [rng.randint(0, 97, n).tolist() for n in (5, 23, 3, 20, 9)]
    with LLMEngine(_tiny_gpt(), max_seqs=4, page_size=4, num_pages=128,
                   prefill_chunk=8, prefix_cache=False) as eng:
        eng.generate(prompts, max_new_tokens=12)    # compile every shape
        early = eng.submit(prompts[0], max_new_tokens=80)
        time.sleep(0.05)
        assert tracing.finished_spans() == []       # no session: nothing
        jax.profiler.start_trace(str(tmp_path))
        futs = [eng.submit(p, max_new_tokens=12) for p in prompts]
        tail = eng.submit(prompts[4], max_new_tokens=70)
        outs = [f.result(120) for f in futs]
        jax.profiler.stop_trace()           # `tail` is still decoding
        outs += [tail.result(120), early.result(120)]
    assert all(len(o["output_ids"]) for o in outs)
    spans = tracing.finished_spans()
    phases = sorted((s for s in spans
                     if s["name"].startswith(ENGINE_PHASES)),
                    key=lambda s: s["ts"])
    names = set(_names(phases))
    assert {"llm.loop.admit", "llm.issue.mixed", "llm.drain.wait",
            "llm.drain.emit"} <= names
    assert len({s["tid"] for s in phases}) == 1     # the engine thread
    assert all(s["parent_id"] is None for s in phases)
    for a, b in zip(phases, phases[1:]):            # flat: no nest, no overlap
        assert b["ts"] >= a["ts"] + a["dur"], (a["name"], b["name"])
    mixed = {s["attrs"]["issue_seq"]: s for s in phases
             if s["name"] == "llm.issue.mixed"}
    assert mixed and all(
        {"live_rows", "chunk_rows", "chunk_tokens", "ticks"} <= set(
            s["attrs"]) for s in mixed.values())
    emits = {s["attrs"]["issue_seq"]: s for s in phases
             if s["name"] == "llm.drain.emit"}
    # the request submitted before the session has no tree; the six rooted
    # inside it have all four phases, also the one it ended on mid-decode
    roots = [s for s in spans if s["name"] == "llm.request"]
    assert len(roots) == 6
    for root in roots:
        kids = {s["name"]: s for s in spans
                if s["parent_id"] == root["span_id"]}
        assert set(kids) == {"llm.queue", "llm.prefill", "llm.first_token",
                             "llm.decode"}
        chunks = [e for e in kids["llm.prefill"]["events"]
                  if e["name"] == "chunk"]
        assert chunks
        for ch in chunks:
            # a chunk is stamped with the read its issue phase started from
            ph = mixed.get(ch["attrs"]["issue_seq"])
            assert ph is None or ch["ts"] == ph["ts"]
        assert any(ch["attrs"]["issue_seq"] in mixed for ch in chunks)
        # the first token ends at the fetch that delivered it: inside the
        # emit phase of the dispatch that carried the prompt's last chunk
        ft = kids["llm.first_token"]
        emit = emits.get(chunks[-1]["attrs"]["issue_seq"])
        if emit is not None:
            assert emit["ts"] <= ft["ts"] + ft["dur"] \
                <= emit["ts"] + emit["dur"]
    host = _host_events(str(tmp_path))
    on_clock = {n for n, _, _ in host}
    assert {"llm.issue.mixed", "llm.drain.wait", "llm.drain.emit",
            "llm.loop.admit"} <= on_clock
    # containers and cross-thread request spans stay off the profiler's clock
    assert not {"llm.request", "llm.queue", "llm.prefill", "llm.decode",
                "llm.first_token"} & on_clock


def test_serve_llm_handler_thread_stays_off_the_profilers_clock(tmp_path):
    """A request over HTTP during a session gets its tree, and the handler
    thread, which only waits for the engine, puts nothing on the profiler's
    clock: an annotation there would cover, and be blamed for, every idle
    gap of the engine thread."""
    import json
    import urllib.request
    from paddle_tpu.inference.llm import LLMEngine, serve_llm
    with LLMEngine(_tiny_gpt(), max_seqs=2, page_size=4, num_pages=64,
                   prefill_chunk=8) as eng:
        srv = serve_llm(eng)
        try:
            url = "http://%s:%d/generate" % srv.server_address[:2]
            jax.profiler.start_trace(str(tmp_path))
            req = urllib.request.Request(
                url, json.dumps({"prompt_ids": [1, 2, 3],
                                 "max_new_tokens": 6}).encode())
            try:
                with urllib.request.urlopen(req, timeout=120) as r:
                    assert r.status == 200
            finally:
                jax.profiler.stop_trace()
        finally:
            srv.shutdown()
            srv.server_close()
    spans = tracing.finished_spans()
    root = next(s for s in spans if s["name"] == "llm.request")
    kids = {s["name"] for s in spans if s["parent_id"] == root["span_id"]}
    assert {"llm.queue", "llm.prefill", "llm.first_token",
            "llm.decode"} <= kids
    phases = [s for s in spans if s["name"].startswith(ENGINE_PHASES)]
    assert phases and len({s["tid"] for s in phases}) == 1
    assert root["tid"] != phases[0]["tid"]      # rooted by the handler
    ours = [(n, d) for n, _, d in _host_events(str(tmp_path))
            if n.startswith(("llm.", "http."))]
    assert ours and all(n.startswith(ENGINE_PHASES) for n, _ in ours)


def test_fit_phases_follow_a_bare_session_and_feed_the_summary(tmp_path):
    from paddle_tpu import nn
    from paddle_tpu.io import TensorDataset
    pt.seed(0)
    net = nn.Sequential(nn.Linear(8, 16), nn.ReLU(), nn.Linear(16, 2))
    m = pt.Model(net)
    m.prepare(optimizer=pt.optimizer.SGD(learning_rate=0.1, parameters=net),
              loss=nn.CrossEntropyLoss())
    x = np.random.RandomState(0).randn(64, 8).astype(np.float32)
    y = np.random.RandomState(1).randint(0, 2, (64, 1))
    data = TensorDataset([x, y])
    m.fit(data, batch_size=16, epochs=1, verbose=0)     # warm, no session
    assert tracing.finished_spans() == []
    jax.profiler.start_trace(str(tmp_path))
    m.fit(data, batch_size=16, epochs=1, verbose=0)
    jax.profiler.stop_trace()
    spans = tracing.finished_spans()
    by = {}
    for s in spans:
        by.setdefault(s["name"], []).append(s)
    assert len(by["fit.dispatch"]) == 4 and len(by["fit.callbacks"]) == 4
    assert len(by["fit.next_batch"]) == 5       # the fifth finds the end
    (epoch,) = by["train.epoch"]
    fit = sorted(by["fit.dispatch"] + by["fit.callbacks"]
                 + by["fit.next_batch"], key=lambda s: s["ts"])
    assert all(s["parent_id"] == epoch["span_id"] for s in fit)
    for a, b in zip(fit, fit[1:]):
        assert b["ts"] >= a["ts"] + a["dur"]
    # the per-step span still parents under the epoch, not under the phase
    assert all(s["parent_id"] == epoch["span_id"] for s in by["train.step"])
    on_clock = {n for n, _, _ in _host_events(str(tmp_path))}
    assert {"fit.next_batch", "fit.dispatch", "fit.callbacks"} <= on_clock
    assert not {"train.epoch", "train.step"} & on_clock
