"""Request-scoped tracing, debug server, flight recorder (observability
tentpole 2): span identity/nesting semantics (incl. cross-thread
trees), ring-buffer bounds, the merged chrome-trace export with
metadata + per-profiler window filtering, a live /metrics + /statusz
round-trip on an ephemeral port, the LLM request span-tree acceptance
(children tile submit→finish), and the crash paths — SIGTERM and
atexit dumps via real subprocesses."""

import json
import os
import signal
import subprocess
import sys
import threading
import time
import urllib.request

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu.observability import (export_chrome_tracing, flight,
                                      server, tracing)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean_tracing():
    tracing.clear()
    tracing.enable()
    yield
    tracing.disable()
    tracing.clear()
    tracing.set_capacity(tracing.DEFAULT_TABLE_CAP)


def _run_py(code: str, timeout=180):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    return subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True,
                          timeout=timeout)


# ---------------------------------------------------------------------------
# span semantics
# ---------------------------------------------------------------------------

def test_span_ids_attrs_events_and_thread_local_nesting():
    with tracing.span("outer", attrs={"a": 1}) as outer:
        assert tracing.current_span() is outer
        with tracing.span("inner") as inner:
            inner.add_event("tick", {"n": 1})
            assert inner.parent_id == outer.span_id
            assert inner.trace_id == outer.trace_id
    assert tracing.current_span() is None
    fin = {s["name"]: s for s in tracing.finished_spans()}
    assert fin["outer"]["parent_id"] is None
    assert fin["outer"]["attrs"] == {"a": 1}
    assert fin["inner"]["events"][0]["name"] == "tick"
    assert fin["inner"]["dur"] >= 0
    # inner ended first: ring order is end order
    names = [s["name"] for s in tracing.finished_spans()]
    assert names == ["inner", "outer"]


def test_span_nesting_across_threads_via_explicit_parent():
    """The LLM pattern: root on the submitter thread, phases on the
    engine loop thread, linked by carrying the parent explicitly."""
    root = tracing.start_span("req", parent=None)
    done = threading.Event()
    out = {}

    def worker():
        child = tracing.start_span("phase", parent=root)
        grand = tracing.start_span("sub", parent=child)
        grand.end()
        child.end()
        out["child"], out["grand"] = child, grand
        done.set()

    threading.Thread(target=worker, name="engine-loop").start()
    assert done.wait(10)
    root.end()
    assert out["child"].parent_id == root.span_id
    assert out["child"].trace_id == root.trace_id
    assert out["grand"].parent_id == out["child"].span_id
    assert out["grand"].trace_id == root.trace_id
    by_name = {s["name"]: s for s in tracing.finished_spans()}
    assert by_name["phase"]["tname"] == "engine-loop"
    assert by_name["req"]["tname"] != "engine-loop"


def test_span_end_is_idempotent_and_error_status_recorded():
    sp = tracing.start_span("x")
    sp.end()
    t1 = sp.t1
    sp.end()                      # second end: no-op
    assert sp.t1 == t1
    assert len(tracing.finished_spans()) == 1
    with pytest.raises(RuntimeError):
        with tracing.span("boom"):
            raise RuntimeError("dead")
    fin = [s for s in tracing.finished_spans() if s["name"] == "boom"][0]
    assert fin["status"] == "error"
    assert "dead" in fin["attrs"]["error"]


def test_ring_buffer_overflow_keeps_newest():
    tracing.set_capacity(8)
    for i in range(30):
        tracing.start_span(f"s{i}").end()
    fin = tracing.finished_spans()
    assert len(fin) == 8
    assert [s["name"] for s in fin] == [f"s{i}" for i in range(22, 30)]
    # live spans are not bounded by the ring and survive overflow
    live = tracing.start_span("still-going")
    assert [s["name"] for s in tracing.live_spans()] == ["still-going"]
    live.end()


def test_per_span_event_cap():
    sp = tracing.start_span("chatty")
    for i in range(tracing.MAX_EVENTS_PER_SPAN + 50):
        sp.add_event("e", {"i": i})
    sp.end()
    d = tracing.finished_spans()[-1]
    assert len(d["events"]) == tracing.MAX_EVENTS_PER_SPAN
    assert d["dropped_events"] == 50


def test_disabled_tracing_is_noop():
    tracing.disable()
    sp = tracing.start_span("ghost")
    assert sp is tracing.NOOP_SPAN
    sp.add_event("x").set_attr("y", 1)
    sp.end()
    with tracing.span("ghost2"):
        assert tracing.current_span() is None
    assert tracing.finished_spans() == []
    assert tracing.live_spans() == []


def test_rollup_aggregates_by_name():
    for _ in range(3):
        tracing.start_span("llm.prefill").end()
    tracing.start_span("llm.decode").end()
    tracing.start_span("llm.request").end()
    r = tracing.rollup(prefix="llm.")
    assert r["llm.prefill"]["count"] == 3
    assert r["llm.decode"]["count"] == 1
    assert abs(sum(v["share"] for v in r.values()) - 1.0) < 0.01
    # exclude drops a name from output AND the share denominator
    # (phase shares over the spans that tile a root must sum to 1)
    r = tracing.rollup(prefix="llm.", exclude=("llm.request",))
    assert "llm.request" not in r
    assert abs(sum(v["share"] for v in r.values()) - 1.0) < 0.01


# ---------------------------------------------------------------------------
# chrome export: merged timeline, metadata, window filter
# ---------------------------------------------------------------------------

def test_chrome_export_merges_spans_with_metadata(tmp_path):
    from paddle_tpu import profiler
    prof = profiler.Profiler(log_dir=str(tmp_path / "prof"))
    prof.start()
    with profiler.RecordEvent("host_ann"):
        pass
    root = tracing.start_span("req", attrs={"k": "v"})
    child = tracing.start_span("phase", parent=root)
    child.add_event("mark", {"n": 3})
    child.end()
    root.end()
    prof.stop()
    path = export_chrome_tracing(prof, str(tmp_path / "trace.json"))
    trace = json.load(open(path))
    evs = trace["traceEvents"]
    md = [e for e in evs if e["ph"] == "M"]
    assert any(e["name"] == "process_name" for e in md)
    tnames = [e for e in md if e["name"] == "thread_name"]
    assert tnames and all(e["args"]["name"] for e in tnames)
    xs = {e["name"]: e for e in evs if e["ph"] == "X"}
    assert "host_ann" in xs                      # RecordEvent stream
    assert xs["req"]["cat"] == "span"
    assert xs["phase"]["args"]["parent_id"] == \
        xs["req"]["args"]["span_id"]             # parent link survives
    assert xs["req"]["args"]["k"] == "v"
    instants = [e for e in evs if e["ph"] == "i"]
    assert any(e["name"] == "phase:mark" and e["args"]["n"] == 3
               for e in instants)
    # span fed summary() stats (one timeline, one aggregate table)...
    assert "req" in prof.summary()
    # ...but renders exactly once in the trace
    assert sum(1 for e in evs if e["ph"] == "X" and e["name"] == "req") \
        == 1


def test_chrome_export_filters_to_profiler_window(tmp_path):
    from paddle_tpu import profiler
    prof = profiler.Profiler(
        scheduler=profiler.make_scheduler(closed=1, ready=0, record=1),
        log_dir=str(tmp_path / "prof"))
    prof.start()                       # step 0: CLOSED (no window)
    with profiler.RecordEvent("outside"):
        pass
    tracing.start_span("span_outside").end()
    prof.step()                        # step 1: RECORD_AND_RETURN
    with profiler.RecordEvent("inside"):
        pass
    tracing.start_span("span_inside").end()
    prof.stop()
    filtered = json.load(open(export_chrome_tracing(
        prof, str(tmp_path / "f.json"))))
    names = {e["name"] for e in filtered["traceEvents"]
             if e["ph"] == "X"}
    assert "inside" in names and "span_inside" in names
    assert "outside" not in names and "span_outside" not in names
    everything = json.load(open(export_chrome_tracing(
        None, str(tmp_path / "all.json"))))
    names = {e["name"] for e in everything["traceEvents"]
             if e["ph"] == "X"}
    assert {"inside", "outside", "span_inside",
            "span_outside"} <= names


# ---------------------------------------------------------------------------
# debug server round-trip (ephemeral port)
# ---------------------------------------------------------------------------

def _get(url):
    with urllib.request.urlopen(url, timeout=30) as r:
        return r.status, r.read()


def test_debug_server_roundtrip(tmp_path):
    from paddle_tpu.observability import default_registry
    reg = default_registry()
    reg.counter("debug_server_test_total", "probe").inc(7)
    server.register_status_provider(
        "test_component", lambda: {"answer": 42})
    tracing.start_span("visible.span").end()
    srv = server.DebugServer(port=0).start()
    try:
        base = f"http://127.0.0.1:{srv.port}"
        code, body = _get(base + "/healthz")
        assert code == 200 and json.loads(body)["status"] == "ok"

        code, body = _get(base + "/metrics")
        text = body.decode()
        assert code == 200
        assert "debug_server_test_total 7.0" in text
        for line in text.splitlines():        # 0.0.4 exposition parses
            if not line or line.startswith("#"):
                continue
            _, value = line.rsplit(" ", 1)
            float(value if value != "+Inf" else "inf")

        code, body = _get(base + "/statusz")
        st = json.loads(body)
        assert code == 200
        assert st["providers"]["test_component"] == {"answer": 42}
        assert st["tracing_enabled"] is True
        assert "device_memory" in st

        code, body = _get(base + "/tracez?limit=10")
        tz = json.loads(body)
        assert code == 200
        assert any(s["name"] == "visible.span" for s in tz["finished"])

        with pytest.raises(urllib.error.HTTPError) as ei:
            _get(base + "/nope")
        assert ei.value.code == 404
    finally:
        srv.stop()
        server.unregister_status_provider("test_component")


def test_debug_server_profilez_arms_one_window(tmp_path):
    srv = server.DebugServer(port=0).start()
    try:
        base = f"http://127.0.0.1:{srv.port}"
        body = json.dumps({"duration_s": 0.4,
                           "log_dir": str(tmp_path / "od")}).encode()
        req = urllib.request.Request(base + "/profilez", data=body,
                                     method="POST")
        with urllib.request.urlopen(req, timeout=30) as r:
            armed = json.loads(r.read())["armed"]
        assert armed["duration_s"] == 0.4
        # second arm while the window is open → 409
        req2 = urllib.request.Request(base + "/profilez", data=body,
                                      method="POST")
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(req2, timeout=30)
        assert ei.value.code == 409
        deadline = time.time() + 15
        while time.time() < deadline:       # window closes on its own
            code, b = _get(base + "/profilez")
            if json.loads(b)["armed"] is None:
                break
            time.sleep(0.1)
        else:
            pytest.fail("profiler window never disarmed")
        assert os.path.isdir(str(tmp_path / "od"))  # trace dir created
    finally:
        srv.stop()


def test_dead_component_drops_out_of_statusz():
    class Thing:
        pass

    import weakref
    t = Thing()
    ref = weakref.ref(t)
    server.register_status_provider(
        "ephemeral", lambda: {"up": 1} if ref() is not None else None)
    assert server._collect_status()["ephemeral"] == {"up": 1}
    del t
    assert "ephemeral" not in server._collect_status()
    assert "ephemeral" not in server._providers   # self-unregistered


# ---------------------------------------------------------------------------
# LLM request span-tree acceptance
# ---------------------------------------------------------------------------

def _tiny_gpt():
    from paddle_tpu.models.gpt import GPTForCausalLM, gpt_config
    pt.seed(0)
    cfg = gpt_config("gpt2-small", num_layers=2, hidden_size=64,
                     num_heads=4, vocab_size=97,
                     max_position_embeddings=96, hidden_dropout=0.0,
                     attention_dropout=0.0)
    return GPTForCausalLM(cfg)


def test_llm_request_span_tree_parents_and_latency_sum(tmp_path):
    """Acceptance: with tracing enabled, each request leaves a
    queue→prefill→first_token→decode tree parented under one
    llm.request root whose children tile the request's observed
    end-to-end latency (±5%), and the chrome export carries it."""
    from paddle_tpu.inference.llm import LLMEngine
    net = _tiny_gpt()
    rng = np.random.RandomState(0)
    prompts = [rng.randint(0, 97, n).tolist() for n in (5, 11, 3)]
    with LLMEngine(net, max_seqs=4, page_size=4, num_pages=128,
                   prefill_chunk=16) as eng:
        outs = eng.generate(prompts, max_new_tokens=8)
    spans = tracing.finished_spans()
    roots = [s for s in spans if s["name"] == "llm.request"]
    assert len(roots) == 3
    for root, out in zip(sorted(roots,
                                key=lambda s: s["attrs"]["nonce"]),
                         outs):
        kids = [s for s in spans
                if s["parent_id"] == root["span_id"]]
        by_name = {k["name"]: k for k in kids}
        assert set(by_name) == {"llm.queue", "llm.prefill",
                                "llm.first_token", "llm.decode"}
        for k in kids:
            assert k["trace_id"] == root["trace_id"]
        # phases tile: each child starts where the previous ended
        order = [by_name[n] for n in ("llm.queue", "llm.prefill",
                                      "llm.first_token", "llm.decode")]
        for a, b in zip(order, order[1:]):
            assert b["ts"] == pytest.approx(a["ts"] + a["dur"])
        child_sum = sum(k["dur"] for k in kids)
        assert child_sum == pytest.approx(root["dur"], rel=1e-6)
        assert child_sum == pytest.approx(out["latency_s"], rel=0.05)
        assert root["attrs"]["outcome"] == "completed"
        assert root["attrs"]["output_tokens"] == 8
        # prefill carries per-chunk + cache annotations
        assert "cache_hit_tokens" in by_name["llm.prefill"]["attrs"]
        assert any(e["name"] == "chunk"
                   for e in by_name["llm.prefill"]["events"])
        assert any(e["name"] == "first_token"
                   for e in root["events"])
    # the chrome export renders the tree with parent links in args
    trace = json.load(open(export_chrome_tracing(
        None, str(tmp_path / "llm.json"))))
    xs = [e for e in trace["traceEvents"] if e.get("cat") == "span"]
    root_ids = {e["args"]["span_id"] for e in xs
                if e["name"] == "llm.request"}
    decode_parents = {e["args"]["parent_id"] for e in xs
                      if e["name"] == "llm.decode"}
    assert decode_parents <= root_ids
    # no live spans left behind after a clean engine shutdown
    assert tracing.live_spans() == []


def test_llm_failed_admission_closes_span_tree_with_error():
    from paddle_tpu.inference.llm import LLMEngine
    net = _tiny_gpt()
    with LLMEngine(net, max_seqs=1, page_size=4, num_pages=4,
                   prefill_chunk=16) as eng:
        fut = eng.submit(list(range(20)), max_new_tokens=2)
        with pytest.raises(ValueError, match="cannot fit"):
            fut.result(timeout=120)
    roots = [s for s in tracing.finished_spans()
             if s["name"] == "llm.request"]
    assert len(roots) == 1
    assert roots[0]["status"] == "error"
    assert roots[0]["attrs"]["outcome"] == "failed"
    assert tracing.live_spans() == []


def test_llm_statusz_provider_lifecycle():
    from paddle_tpu.inference.llm import LLMEngine
    net = _tiny_gpt()
    eng = LLMEngine(net, max_seqs=2, page_size=4, num_pages=64,
                    prefill_chunk=8)
    st = server._collect_status()
    mine = [v for k, v in st.items() if k.startswith("llm_engine_")]
    assert any(v["max_seqs"] == 2 and "prefix_cache" in v
               for v in mine)
    eng.close()
    st = server._collect_status()
    assert eng._status_name not in st


# ---------------------------------------------------------------------------
# train-loop spans
# ---------------------------------------------------------------------------

def test_model_fit_epoch_dispatch_drain_spans():
    from paddle_tpu import nn
    from paddle_tpu.io import TensorDataset
    from paddle_tpu.metric import Accuracy

    pt.seed(0)
    net = nn.Sequential(nn.Linear(8, 16), nn.ReLU(), nn.Linear(16, 2))
    m = pt.Model(net)
    m.prepare(optimizer=pt.optimizer.SGD(learning_rate=0.1,
                                         parameters=net),
              loss=nn.CrossEntropyLoss(), metrics=[Accuracy()])
    x = np.random.RandomState(0).randn(64, 8).astype(np.float32)
    y = np.random.RandomState(1).randint(0, 2, (64, 1))
    m.fit(TensorDataset([x, y]), batch_size=16, epochs=2, verbose=0,
          steps_per_loop=2)
    spans = tracing.finished_spans()
    epochs = [s for s in spans if s["name"] == "train.epoch"]
    assert [s["attrs"]["epoch"] for s in epochs] == [0, 1]
    dispatches = [s for s in spans if s["name"] == "train.dispatch"]
    assert len(dispatches) == 4                    # 2 slabs × 2 epochs
    epoch_ids = {s["span_id"] for s in epochs}
    assert all(d["parent_id"] in epoch_ids for d in dispatches)
    assert all(d["attrs"]["k"] == 2 for d in dispatches)
    # first dispatch compiled → recompile event attached
    first = min(dispatches, key=lambda s: s["ts"])
    assert any(e["name"] == "recompile" for e in first["events"])
    assert sum(1 for d in dispatches
               for e in d["events"] if e["name"] == "recompile") == 1
    drains = [s for s in spans if s["name"] == "train.metric_drain"]
    assert drains and all(d["parent_id"] in epoch_ids or
                          d["parent_id"] is None for d in drains)
    # loader waits surfaced as spans too
    assert any(s["name"] == "io.next_wait" for s in spans)
    # the /statusz provider reflects trained state
    st = server._collect_status()
    mine = [v for k, v in st.items() if k.startswith("train_model_")]
    assert any(v["step_count"] == 8 and v["loop_compiled"]
               for v in mine)


def test_chrome_export_keeps_spans_overlapping_window(tmp_path):
    """A long-lived root that STARTED before the RECORD window but
    runs through it must export (interval overlap, not point-in-
    window), or its in-window children would carry dangling
    parent_ids; a profiler that never opened a window exports
    everything it recorded instead of an empty file."""
    from paddle_tpu import profiler
    prof = profiler.Profiler(
        scheduler=profiler.make_scheduler(closed=1, ready=0, record=1),
        log_dir=str(tmp_path / "prof"))
    prof.start()                        # step 0: CLOSED
    root = tracing.start_span("long.root")     # starts pre-window
    prof.step()                         # step 1: window opens
    tracing.start_span("child", parent=root).end()
    root.end()                          # ends inside the window
    prof.stop()
    trace = json.load(open(export_chrome_tracing(
        prof, str(tmp_path / "t.json"))))
    xs = {e["name"]: e for e in trace["traceEvents"]
          if e.get("cat") == "span"}
    assert "long.root" in xs and "child" in xs
    assert xs["child"]["args"]["parent_id"] == \
        xs["long.root"]["args"]["span_id"]
    # windowless profiler (never reached RECORD): export everything
    prof2 = profiler.Profiler(
        scheduler=lambda step: profiler.ProfilerState.CLOSED,
        log_dir=str(tmp_path / "p2"))
    prof2.start()
    tracing.start_span("recorded.anyway").end()
    prof2.stop()
    trace = json.load(open(export_chrome_tracing(
        prof2, str(tmp_path / "t2.json"))))
    assert any(e["name"] == "recorded.anyway"
               for e in trace["traceEvents"])


def test_profiler_stop_does_not_kill_newer_profiler(tmp_path):
    """A stale stop() (the /profilez timed disarm pattern) must not
    deactivate a profiler started after it."""
    from paddle_tpu import profiler
    a = profiler.Profiler(log_dir=str(tmp_path / "a"))
    a.start()
    a._stop_trace()                     # release the jax trace slot
    b = profiler.Profiler(log_dir=str(tmp_path / "b"))
    b.start()                           # b now owns the event stream
    b._stop_trace()
    a.stop()                            # stale stop: must be a no-op
    assert profiler._events.active is True
    b.stop()
    assert profiler._events.active is False


def test_train_batch_exception_closes_step_span():
    """A dispatch failure must not leak a live span (the _live
    registry is uncapped) when the caller catches and continues."""
    from paddle_tpu import nn
    pt.seed(0)
    net = nn.Sequential(nn.Linear(8, 4))
    m = pt.Model(net)
    m.prepare(optimizer=pt.optimizer.SGD(learning_rate=0.1,
                                         parameters=net),
              loss=nn.CrossEntropyLoss())
    x = np.zeros((4, 8), np.float32)
    y = np.zeros((4, 1), np.int64)
    m.train_batch([x], [y])             # compile the good shape
    m._train_step_fn = None             # force rebuild...

    def boom(*a, **kw):
        raise RuntimeError("device fell over")

    m._build_train_step = lambda: boom
    with pytest.raises(RuntimeError, match="fell over"):
        m.train_batch([x], [y])
    assert not any(s["name"] == "train.step"
                   for s in tracing.live_spans())
    bad = [s for s in tracing.finished_spans()
           if s["name"] == "train.step" and s["status"] == "error"]
    assert len(bad) == 1


def test_fit_exception_closes_epoch_span():
    """A step failure unwinding out of fit() must not leave the epoch
    span on the thread-local stack (a caller catching the error and
    re-running fit would otherwise parent under a dead epoch) or in
    the live-span registry."""
    from paddle_tpu import nn
    from paddle_tpu.hapi.callbacks import Callback
    from paddle_tpu.io import TensorDataset

    class Bomb(Callback):
        def on_train_batch_end(self, step, logs=None):
            raise RuntimeError("boom")

    pt.seed(0)
    net = nn.Sequential(nn.Linear(8, 4))
    m = pt.Model(net)
    m.prepare(optimizer=pt.optimizer.SGD(learning_rate=0.1,
                                         parameters=net),
              loss=nn.CrossEntropyLoss())
    x = np.zeros((16, 8), np.float32)
    y = np.zeros((16, 1), np.int64)
    with pytest.raises(RuntimeError, match="boom"):
        m.fit(TensorDataset([x, y]), batch_size=8, epochs=1, verbose=0,
              callbacks=[Bomb()])
    assert tracing.current_span() is None
    assert not any(s["name"] == "train.epoch"
                   for s in tracing.live_spans())
    ep = [s for s in tracing.finished_spans()
          if s["name"] == "train.epoch"]
    assert len(ep) == 1 and ep[0]["status"] == "error"


def test_profilez_refuses_while_job_profiler_records(tmp_path):
    """Arming the on-demand window while the job's own Profiler is
    recording would clear (then disable) the process-wide event
    tables — the arm must refuse instead."""
    from paddle_tpu import profiler
    prof = profiler.Profiler(log_dir=str(tmp_path / "job"))
    prof.start()
    try:
        srv = server.DebugServer(port=0)
        assert srv._arm.arm(0.2, str(tmp_path / "od")) is None
        srv._httpd.server_close()
    finally:
        prof.stop()


# ---------------------------------------------------------------------------
# flight recorder
# ---------------------------------------------------------------------------

def test_flight_recorder_dump_format(tmp_path):
    from paddle_tpu.observability import default_registry
    default_registry().counter("flight_probe_total").inc(2)
    tracing.start_span("done.work").end()
    live = tracing.start_span("inflight.work", attrs={"slot": 3})
    rec = flight.FlightRecorder(str(tmp_path))
    path = rec.dump("unit")
    live.end()
    assert path and os.path.exists(path)
    rows = [json.loads(ln) for ln in open(path)]
    header = rows[0]
    assert header["kind"] == "header" and header["reason"] == "unit"
    assert header["metrics"]["flight_probe_total"] == 2
    by_kind = {}
    for r in rows[1:]:
        by_kind.setdefault(r["kind"], []).append(r)
    live_names = [r["name"] for r in by_kind["span"] if r["live"]]
    done_names = [r["name"] for r in by_kind["span"] if not r["live"]]
    assert "inflight.work" in live_names
    assert "done.work" in done_names
    assert all("ts_wall" in r for r in by_kind["span"])


def test_flight_recorder_thread_exception_hook(tmp_path, monkeypatch):
    # silence the default hook's traceback print for this test
    monkeypatch.setattr(threading, "excepthook", lambda args: None)
    rec = flight.FlightRecorder(str(tmp_path)).install()
    try:
        t = threading.Thread(target=lambda: 1 / 0)
        t.start()
        t.join(timeout=30)
        files = os.listdir(str(tmp_path))
        assert any("thread_exception" in f for f in files)
    finally:
        rec.uninstall()


def test_sigterm_dumps_inflight_spans_subprocess(tmp_path):
    """Acceptance: kill a worker with SIGTERM → a flight-recorder
    JSONL containing the in-flight spans is left behind, and the
    process still dies BY SIGTERM (supervisors key off the wait
    status)."""
    out = str(tmp_path)
    code = f"""
import os, signal, sys, time
import jax; jax.config.update("jax_platforms", "cpu")
from paddle_tpu.observability import tracing, flight
tracing.enable()
flight.install_flight_recorder({out!r})
tracing.start_span("request.inflight", attrs={{"slot": 1}})
os.kill(os.getpid(), signal.SIGTERM)
time.sleep(60)   # unreachable: the re-raised SIGTERM kills us
"""
    p = _run_py(code)
    assert p.returncode == -signal.SIGTERM, (p.returncode, p.stderr)
    dumps = [f for f in os.listdir(out) if f.endswith(".jsonl")]
    assert len(dumps) == 1 and "sigterm" in dumps[0]
    rows = [json.loads(ln) for ln in open(os.path.join(out, dumps[0]))]
    assert rows[0]["reason"] == "sigterm"
    live = [r for r in rows if r.get("kind") == "span" and r["live"]]
    assert any(r["name"] == "request.inflight" for r in live)


def test_preemption_guard_dumps_flight_record(tmp_path):
    from paddle_tpu.distributed.elastic import PreemptionGuard
    rec = flight.install_flight_recorder(str(tmp_path))
    try:
        guard = PreemptionGuard(install=False)
        tracing.start_span("step.inflight")
        guard.trigger()
        assert guard.check(exit=False) is True
        files = [f for f in os.listdir(str(tmp_path))
                 if "preemption" in f]
        assert len(files) == 1
        rows = [json.loads(ln) for ln in
                open(os.path.join(str(tmp_path), files[0]))]
        assert any(r.get("kind") == "span" and r["live"] and
                   r["name"] == "step.inflight" for r in rows)
    finally:
        rec.uninstall()


def test_jsonl_reporter_atexit_flush_subprocess(tmp_path):
    """Satellite: a reporter never stopped still writes its final
    snapshot at interpreter exit — short-lived jobs whose whole life
    fits inside one interval lose nothing."""
    path = str(tmp_path / "m.jsonl")
    code = f"""
import jax; jax.config.update("jax_platforms", "cpu")
from paddle_tpu import observability as obs
obs.default_registry().counter("atexit_probe_total").inc(3)
rep = obs.JSONLReporter({path!r}, interval=3600)
# exit WITHOUT stop(): atexit must flush the final snapshot
"""
    p = _run_py(code)
    assert p.returncode == 0, p.stderr
    rows = [json.loads(ln) for ln in open(path) if ln.strip()]
    assert len(rows) >= 1
    assert rows[-1]["metrics"]["atexit_probe_total"] == 3
