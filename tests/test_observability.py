"""Unified metrics + trace-export layer (observability tentpole):
metrics core semantics, percentile math at bucket boundaries, the
make_scheduler edge cases, every exporter's output format, and the
end-to-end acceptance — a Model.fit + LLMEngine smoke run must leave
non-empty TTFT/tokens-per-sec histograms and step-time metrics in BOTH
the Prometheus text and JSONL exports."""

import json
import math
import threading

import numpy as np
import pytest

from paddle_tpu import observability as obs
from paddle_tpu import profiler
from paddle_tpu.observability import (JSONLReporter, MetricRegistry,
                                      export_chrome_tracing,
                                      prometheus_text)


@pytest.fixture()
def registry():
    return MetricRegistry()


@pytest.fixture()
def clean_default_registry():
    reg = obs.default_registry()
    reg.reset()
    yield reg
    reg.reset()


# ---------------------------------------------------------------------------
# metrics core
# ---------------------------------------------------------------------------

def test_counter_monotonic(registry):
    c = registry.counter("reqs", "requests")
    c.inc()
    c.inc(4)
    assert c.value == 5
    with pytest.raises(ValueError):
        c.inc(-1)


def test_gauge_set_inc_dec(registry):
    g = registry.gauge("occupancy")
    g.set(0.5)
    g.inc(0.25)
    g.dec(0.5)
    assert g.value == pytest.approx(0.25)


def test_labels_vend_independent_series(registry):
    c = registry.counter("rpc", label_names=("method", "code"))
    c.labels(method="gen", code="200").inc(3)
    c.labels("gen", "500").inc()
    assert c.labels(method="gen", code="200").value == 3
    assert c.labels(method="gen", code="500").value == 1
    with pytest.raises(ValueError):
        c.inc()          # labeled family has no default child
    with pytest.raises(ValueError):
        c.labels("only-one")


def test_registry_rejects_kind_and_label_conflicts(registry):
    registry.counter("x")
    with pytest.raises(ValueError):
        registry.gauge("x")
    registry.histogram("h", label_names=("a",))
    with pytest.raises(ValueError):
        registry.histogram("h", label_names=("b",))


def test_registry_get_or_create_idempotent(registry):
    a = registry.counter("same")
    b = registry.counter("same")
    assert a is b


def test_snapshot_flattens_all_kinds(registry):
    registry.counter("c").inc(2)
    registry.gauge("g", label_names=("d",)).labels(d="tpu:0").set(7)
    h = registry.histogram("h", buckets=(1.0, 2.0))
    h.observe(1.5)
    snap = registry.snapshot()
    assert snap["c"] == 2
    assert snap['g{d="tpu:0"}'] == 7
    assert snap["h_count"] == 1 and snap["h_sum"] == 1.5
    assert "h_p50" in snap and "h_p99" in snap


# ---------------------------------------------------------------------------
# histogram bucket/percentile math (satellite: boundary cases)
# ---------------------------------------------------------------------------

def test_histogram_boundary_observation_is_inclusive(registry):
    """Prometheus semantics: le is an INCLUSIVE upper bound — a value
    exactly on a boundary lands in that boundary's bucket."""
    h = registry.histogram("lat", buckets=(1.0, 2.0, 4.0))
    for v in (1.0, 2.0, 4.0, 4.0001):
        h.observe(v)
    cum = dict(h.bucket_counts())
    assert cum[1.0] == 1
    assert cum[2.0] == 2
    assert cum[4.0] == 3
    assert cum[math.inf] == 4


def test_histogram_percentiles_exact_at_boundary(registry):
    # all mass at one boundary value → every quantile reports exactly it
    h = registry.histogram("t", buckets=(1.0, 2.0, 4.0))
    for _ in range(8):
        h.observe(2.0)
    for q in (0.0, 0.5, 0.9, 0.99, 1.0):
        assert h.quantile(q) == pytest.approx(2.0)


def test_histogram_percentile_interpolation_and_clamps(registry):
    h = registry.histogram("t", buckets=(1.0, 2.0))
    for v in (0.5, 1.0, 1.5, 2.0):
        h.observe(v)
    assert h.quantile(0.0) == pytest.approx(0.5)    # clamp to min
    assert h.quantile(1.0) == pytest.approx(2.0)    # clamp to max
    assert h.quantile(0.5) == pytest.approx(1.0)    # boundary rank
    p = h.percentiles((50, 90, 99))
    assert set(p) == {"p50", "p90", "p99"}
    assert p["p50"] <= p["p90"] <= p["p99"] <= 2.0


def test_histogram_overflow_bucket_reports_max(registry):
    # observations beyond the last finite bound live in +Inf: the
    # estimator must not fabricate values above the observed max
    h = registry.histogram("t", buckets=(1.0, 2.0))
    h.observe(100.0)
    h.observe(200.0)
    assert h.quantile(0.9) == pytest.approx(200.0)
    assert h.count == 2 and h.mean == pytest.approx(150.0)


def test_empty_histogram_is_safe(registry):
    h = registry.histogram("t")
    assert h.count == 0 and h.sum == 0.0 and h.mean == 0.0
    assert h.quantile(0.5) == 0.0


# ---------------------------------------------------------------------------
# make_scheduler edge cases (satellite)
# ---------------------------------------------------------------------------

def test_scheduler_skip_first_repeat_interaction():
    """skip_first shifts the whole cycle train; repeat counts cycles
    AFTER the skip — and the tail stays CLOSED forever."""
    S = profiler.ProfilerState
    sched = profiler.make_scheduler(closed=1, ready=1, record=2,
                                    repeat=2, skip_first=3)
    states = [sched(i) for i in range(12)]
    assert states[:3] == [S.CLOSED] * 3                     # skip_first
    cycle = [S.CLOSED, S.READY, S.RECORD, S.RECORD_AND_RETURN]
    assert states[3:7] == cycle
    assert states[7:11] == cycle                            # 2nd repeat
    assert states[11] == S.CLOSED
    assert all(sched(i) == S.CLOSED for i in range(11, 40))


def test_scheduler_single_step_record_cycles():
    """record=1: the only recording step of each cycle IS the cycle
    boundary, so it must be RECORD_AND_RETURN (plain RECORD would never
    close the trace)."""
    S = profiler.ProfilerState
    sched = profiler.make_scheduler(closed=1, ready=0, record=1)
    assert [sched(i) for i in range(4)] == \
        [S.CLOSED, S.RECORD_AND_RETURN] * 2
    # degenerate but legal: record every step, one-step cycles
    sched = profiler.make_scheduler(closed=0, ready=0, record=1)
    assert all(sched(i) == S.RECORD_AND_RETURN for i in range(5))


def test_scheduler_record_and_return_drives_trace_cycles(tmp_path):
    """A RECORD_AND_RETURN → RECORD transition closes one trace and
    opens the next: on_trace_ready fires once per completed cycle."""
    fired = []
    prof = profiler.Profiler(
        scheduler=profiler.make_scheduler(closed=0, ready=0, record=1),
        log_dir=str(tmp_path / "prof"),
        on_trace_ready=lambda p: fired.append(p.step_num))
    prof.start()
    for _ in range(3):
        prof.step()
    prof.stop()
    # one close per step boundary + stop() closing the cycle in flight
    assert fired == [1, 2, 3, 3]


# ---------------------------------------------------------------------------
# profiler host events + race fix
# ---------------------------------------------------------------------------

def test_profiler_start_clear_races_worker_threads(tmp_path):
    """Satellite regression: start() clears the event table under the
    lock while worker threads are mid-RecordEvent — no lost-update
    crashes, and the table still aggregates afterwards."""
    stop = threading.Event()

    def worker():
        while not stop.is_set():
            with profiler.RecordEvent("w"):
                pass

    threads = [threading.Thread(target=worker) for _ in range(4)]
    prof = profiler.Profiler(log_dir=str(tmp_path / "p"))
    prof.start()            # events flowing from line one
    for t in threads:
        t.start()
    try:
        for _ in range(20):
            prof.start()    # repeated clears against concurrent ends
    finally:
        stop.set()
        for t in threads:
            t.join()
        prof.stop()


def test_export_chrome_tracing_complete_events(tmp_path, monkeypatch):
    prof = profiler.Profiler(log_dir=str(tmp_path / "prof"))
    prof.start()
    for _ in range(3):
        with profiler.RecordEvent("step"):
            pass
    with profiler.RecordEvent("save"):
        pass
    prof.stop()
    path = export_chrome_tracing(prof, str(tmp_path / "t" / "trace.json"))
    with open(path) as f:
        trace = json.load(f)          # must json.load cleanly
    events = trace["traceEvents"]
    by_name = {}
    for ev in events:
        if ev["ph"] == "M":           # row-label metadata (Perfetto)
            assert ev["name"] in ("process_name", "thread_name")
            assert ev["args"]["name"]
            continue
        assert ev["ph"] == "X"
        assert ev["dur"] >= 0 and ev["ts"] > 0
        assert isinstance(ev["tid"], int) and isinstance(ev["pid"], int)
        by_name[ev["name"]] = by_name.get(ev["name"], 0) + 1
    assert by_name["step"] == 3       # one X event per annotation
    assert by_name["save"] == 1
    # recording threads get labeled rows, not bare tids
    assert any(ev["ph"] == "M" and ev["name"] == "thread_name"
               for ev in events)
    # profiler module re-exports it (the old `= None` parity marker)
    assert profiler.export_chrome_tracing is export_chrome_tracing


# ---------------------------------------------------------------------------
# exporters
# ---------------------------------------------------------------------------

def test_prometheus_text_format(registry):
    registry.counter("llm.tokens", "tokens out").inc(5)
    registry.gauge("util", label_names=("device",)).labels(
        device="tpu:0").set(0.5)
    h = registry.histogram("lat", buckets=(1.0, 2.0))
    h.observe(0.5)
    h.observe(3.0)
    text = prometheus_text(registry)
    assert "# TYPE llm_tokens counter" in text       # dots sanitized
    assert "llm_tokens 5.0" in text
    assert 'util{device="tpu:0"} 0.5' in text
    assert '# TYPE lat histogram' in text
    assert 'lat_bucket{le="1.0"} 1' in text
    assert 'lat_bucket{le="2.0"} 1' in text
    assert 'lat_bucket{le="+Inf"} 2' in text         # cumulative total
    assert "lat_sum 3.5" in text and "lat_count 2" in text
    # 0.0.4 exposition: every sample line is `name[{labels}] value`
    for line in text.strip().splitlines():
        if line.startswith("#"):
            continue
        _, value = line.rsplit(" ", 1)
        float(value if value != "+Inf" else "inf")


def test_jsonl_reporter_writes_and_shuts_down(tmp_path, registry):
    registry.counter("c").inc(3)
    path = str(tmp_path / "m.jsonl")
    with JSONLReporter(path, interval=0.05, registry=registry):
        import time
        time.sleep(0.2)
        registry.counter("c").inc()
    with open(path) as f:
        rows = [json.loads(ln) for ln in f if ln.strip()]
    assert len(rows) >= 2                 # periodic ticks happened
    assert rows[-1]["metrics"]["c"] == 4  # stop() wrote a final snapshot
    assert all("ts" in r for r in rows)
    rep = JSONLReporter(path, interval=60, registry=registry)
    rep.stop()
    rep.stop()                            # idempotent


def test_sample_device_memory_no_crash_on_cpu(registry):
    # CPU memory_stats() is None — the sampler must cope and not create
    # bogus series
    out = obs.sample_device_memory(registry)
    assert isinstance(out, dict)
    gauge = registry.get("device_memory_bytes")
    assert gauge is not None            # family registered either way


# ---------------------------------------------------------------------------
# StatRegistry is backed by the MetricRegistry
# ---------------------------------------------------------------------------

def test_stat_registry_flows_into_exports(clean_default_registry):
    from paddle_tpu.core.monitor import StatRegistry, stat_add, stat_get
    sreg = StatRegistry.instance()
    sreg.reset()
    stat_add("elastic.restarts")
    stat_add("elastic.restarts", 2)
    sreg.set("lr", 0.1)
    assert stat_get("elastic.restarts") == 3
    snap = sreg.snapshot()
    assert snap["elastic.restarts"] == 3 and snap["lr"] == 0.1
    # the same stats surface through the observability exporters
    text = prometheus_text()
    assert "elastic_restarts 3.0" in text
    assert clean_default_registry.snapshot()["elastic.restarts"] == 3
    sreg.reset()
    assert sreg.snapshot() == {}
    assert stat_get("elastic.restarts") == 0


def test_stat_registry_never_raises_on_typed_name_collisions(
        clean_default_registry):
    """The reference's StatRegistry contract: add/get never raise. A
    stat whose name is already a histogram or labeled family parks
    under a suffixed gauge instead of exploding the call site."""
    from paddle_tpu.core.monitor import StatRegistry, stat_add, stat_get
    sreg = StatRegistry.instance()
    sreg.reset()
    reg = clean_default_registry
    reg.histogram("train_step_seconds").observe(0.5)
    reg.gauge("device_memory_bytes", label_names=("device",))
    stat_add("train_step_seconds", 2)          # collides with histogram
    stat_add("device_memory_bytes")            # collides with labels
    assert stat_get("train_step_seconds") == 2
    assert stat_get("device_memory_bytes") == 1
    assert sreg.snapshot()["train_step_seconds"] == 2
    # reading a typed metric name with no stat behind it returns 0
    sreg.reset()
    assert stat_get("train_step_seconds") == 0
    assert stat_get("device_memory_bytes") == 0
    # ...and the exposition renders both without duplicate names
    text = prometheus_text()
    assert text.count("# TYPE train_step_seconds ") == 1


def test_prometheus_sanitized_name_collision_disambiguated(registry):
    registry.histogram("a.b", buckets=(1.0,)).observe(0.5)
    registry.gauge("a_b").set(3)
    text = prometheus_text(registry)
    type_names = [ln.split()[2] for ln in text.splitlines()
                  if ln.startswith("# TYPE")]
    assert len(set(type_names)) == len(type_names), text


def test_checkpoint_metrics_recorded(tmp_path, clean_default_registry):
    pytest.importorskip("orbax.checkpoint")
    from paddle_tpu.io.checkpoint import CheckpointManager
    with CheckpointManager(str(tmp_path / "ck"), async_save=False) as mgr:
        mgr.save(0, {"w": np.arange(8, dtype=np.float32)})
        mgr.wait_until_finished()
        got = mgr.restore(0)
    assert np.allclose(got["w"], np.arange(8))
    snap = clean_default_registry.snapshot()
    assert snap["checkpoint_save_seconds_count"] == 1
    assert snap["checkpoint_restore_seconds_count"] == 1
    assert snap["checkpoint_bytes_written"] >= 32
    # satellite: the STAT_ADD wiring fires too
    from paddle_tpu.core.monitor import stat_get
    assert stat_get("checkpoint.saves") == 1
    assert stat_get("checkpoint.restores") == 1
    assert stat_get("checkpoint.saved_bytes") >= 32


# ---------------------------------------------------------------------------
# acceptance: instrumented hot paths → non-empty exports
# ---------------------------------------------------------------------------

def test_model_fit_populates_metrics(tmp_path, clean_default_registry):
    import paddle_tpu as pt
    from paddle_tpu import nn
    from paddle_tpu.io import TensorDataset

    pt.seed(0)
    net = nn.Sequential(nn.Linear(8, 16), nn.ReLU(), nn.Linear(16, 2))
    m = pt.Model(net)
    m.prepare(optimizer=pt.optimizer.SGD(learning_rate=0.1,
                                         parameters=net),
              loss=nn.CrossEntropyLoss())
    x = np.random.RandomState(0).randn(64, 8).astype(np.float32)
    y = np.random.RandomState(1).randint(0, 2, (64, 1))
    jsonl = str(tmp_path / "m.jsonl")
    with JSONLReporter(jsonl, interval=60):   # final snapshot on stop
        m.fit(TensorDataset([x, y]), batch_size=16, epochs=2, verbose=0)

    snap = clean_default_registry.snapshot()
    assert snap["train_step_seconds_count"] == 8      # 4 batches × 2
    assert snap["train_step_seconds_p50"] > 0
    assert snap["train_examples_per_second_count"] == 8
    assert snap["train_compile_count"] == 1           # one shape → one
    assert snap["dataloader_batches"] == 8
    assert m.compiled_shape_count == 1

    text = prometheus_text()
    assert "train_step_seconds_count 8" in text
    assert "train_compile_seconds_count 1" in text
    with open(jsonl) as f:
        rows = [json.loads(ln) for ln in f if ln.strip()]
    assert rows[-1]["metrics"]["train_step_seconds_count"] == 8


def test_llm_engine_populates_metrics(clean_default_registry, tmp_path):
    import paddle_tpu as pt
    from paddle_tpu.inference.llm import LLMEngine
    from paddle_tpu.models.gpt import GPTForCausalLM, gpt_config

    pt.seed(0)
    cfg = gpt_config("gpt2-small", num_layers=2, hidden_size=64,
                     num_heads=4, vocab_size=97,
                     max_position_embeddings=96, hidden_dropout=0.0,
                     attention_dropout=0.0)
    net = GPTForCausalLM(cfg)
    rng = np.random.RandomState(0)
    prompts = [rng.randint(0, 97, n).tolist() for n in (5, 9, 3)]
    jsonl = str(tmp_path / "llm.jsonl")
    with JSONLReporter(jsonl, interval=60):
        with LLMEngine(net, max_seqs=4, page_size=4, num_pages=64,
                       prefill_chunk=16) as eng:
            outs = eng.generate(prompts, max_new_tokens=6)
    assert all(len(o["output_ids"]) == 6 for o in outs)

    snap = clean_default_registry.snapshot()
    assert snap["llm_ttft_seconds_count"] == 3        # one per request
    assert snap["llm_ttft_seconds_p90"] > 0
    assert snap["llm_queue_wait_seconds_count"] == 3
    assert snap["llm_decode_tokens_per_second_count"] > 0
    assert snap["llm_decode_tokens_per_second_p50"] > 0
    assert snap["llm_tokens_generated"] == 18         # 3 reqs × 6
    assert snap["llm_requests_completed"] == 3
    assert snap["llm_batch_occupancy_count"] > 0
    assert 'llm_kv_page_utilization' in snap

    text = prometheus_text()
    assert "llm_ttft_seconds_count 3" in text
    assert "llm_decode_tokens_per_second_bucket" in text
    with open(jsonl) as f:
        rows = [json.loads(ln) for ln in f if ln.strip()]
    last = rows[-1]["metrics"]
    assert last["llm_ttft_seconds_count"] == 3
    assert last["llm_decode_tokens_per_second_count"] > 0
