"""Continuous perf observability (ISSUE 11): program cost registry,
live roofline gauges, /perfz surfaces, served-FLOPs attribution, and
fleet MFU federation.

Covers the acceptance criteria:
- /perfz returns live MFU + a step-time breakdown for BOTH a
  ``Model.fit`` run (steps_per_loop>1) and an ``LLMEngine``
  decode-slab run (decode_ticks_per_dispatch>1);
- cost lookups never re-lower (signature-keyed bounded cache in
  cost_model), and a backend with no cost analysis increments
  ``perf_cost_analysis_failures_total`` instead of raising;
- the analytic FLOPs path (``pt.flops`` / the planner formulas) and
  the XLA-counted FLOPs from the cost registry agree within a
  documented tolerance for a transformer block;
- ``fleet_mfu`` reads a down replica as a HOLE (not a zero), and the
  per-tenant served-FLOPs counter survives a nonce-pinned failover
  without double counting.
"""

import json
import urllib.request

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import nn
from paddle_tpu.core import flags
from paddle_tpu.observability import default_registry
from paddle_tpu.observability import perf
from paddle_tpu.observability import server as debug_server


@pytest.fixture(autouse=True)
def _fresh_perf():
    """Each test gets its own PerfRegistry (the metric registry stays
    process-wide, like every other observability test)."""
    perf.reset()
    perf.enable()
    yield
    perf.reset()
    perf.enable()


def _get_json(base, path):
    with urllib.request.urlopen(base + path, timeout=30) as r:
        return json.loads(r.read())


# ---------------------------------------------------------------------------
# peak table + overrides
# ---------------------------------------------------------------------------

def test_peak_table_known_kinds():
    assert perf.peak_flops_for("TPU v5 lite") == 197e12
    assert perf.peak_flops_for("TPU v4") == 275e12
    assert perf.peak_flops_for("TPU v6e") == 918e12
    assert perf.peak_flops_for("cpu") is None
    assert perf.peak_flops_for("") is None


def test_detect_peaks_cpu_fallback_and_override():
    spec = perf.detect_peaks("cpu")
    assert spec.source == "cpu-fallback"
    assert spec.flops > 0 and spec.hbm_bytes_per_s > 0
    spec = perf.detect_peaks("TPU v5 lite")
    assert spec.source == "table" and spec.flops == 197e12 \
        and spec.hbm_bytes_per_s == 819e9
    # the override knob for TPU generations the table doesn't know
    flags.set_flags({"perf_peak_flops": 1.23e15,
                     "perf_peak_hbm_gbps": 2000.0})
    try:
        spec = perf.detect_peaks("TPU v9 hypothetical")
        assert spec.source == "override"
        assert spec.flops == 1.23e15
        assert spec.hbm_bytes_per_s == 2000.0 * 1e9
    finally:
        flags.set_flags({"perf_peak_flops": 0.0,
                         "perf_peak_hbm_gbps": 0.0})


def test_detect_peaks_unknown_tpu_is_an_error():
    """A TPU kind missing from PEAK_TABLE never gets the CPU
    placeholder peaks."""
    with pytest.raises(ValueError, match="PEAK_TABLE"):
        perf.detect_peaks("TPU v9 hypothetical")


def test_bench_peak_delegates_to_one_table():
    import bench
    # CPU backend: bench MFU must read null, not the perf fallback
    assert bench.chip_peak_flops() is None
    flags.set_flags({"perf_peak_flops": 5e13})
    try:
        assert bench.chip_peak_flops() == 5e13
    finally:
        flags.set_flags({"perf_peak_flops": 0.0})


# ---------------------------------------------------------------------------
# cost cache: never re-lowers, failures are counted not raised
# ---------------------------------------------------------------------------

def test_cost_cache_lowers_once_and_caches_failure():
    from paddle_tpu.cost_model import ProgramCostCache
    import jax

    cache = ProgramCostCache()
    calls = {"n": 0}

    def lower():
        calls["n"] += 1
        return jax.jit(lambda x: x @ x).lower(
            jax.ShapeDtypeStruct((16, 16), np.float32))

    a1 = cache.get_or_compute(("k",), lower)
    a2 = cache.get_or_compute(("k",), lower)
    assert calls["n"] == 1, "second lookup re-lowered"
    assert a1 is a2 and a1["flops"] > 0

    boom = {"n": 0}

    def bad():
        boom["n"] += 1
        raise RuntimeError("no analysis on this backend")

    assert cache.get_or_compute(("bad",), bad) is None
    assert cache.get_or_compute(("bad",), bad) is None
    assert boom["n"] == 1, "failure was not cached"


def test_cost_cache_bounded():
    from paddle_tpu.cost_model import ProgramCostCache
    cache = ProgramCostCache(cap=4)
    for i in range(10):
        cache.get_or_compute(("k", i), lambda: (_ for _ in ()).throw(
            RuntimeError("x")))
    assert len(cache) == 4


def test_registry_failure_counter_not_raise():
    reg = perf.instance()
    h = reg.register_program(
        "train", "step", sig=("boom",),
        lower=lambda: (_ for _ in ()).throw(RuntimeError("no backend")))
    h.record(0.01)             # registration already resolved (failed)
    h.record(0.01)
    assert h.cost_failed and not h.cost_resolved
    fam = default_registry().get("perf_cost_analysis_failures_total")
    assert fam is not None and fam.value >= 1
    # payload still renders, the program rides with flops=None
    payload = reg.payload()
    assert payload["cost_failures"] >= 1


def test_program_cap_discipline():
    reg = perf.instance()
    for i in range(perf.PROGRAM_CAP + 10):
        h = reg.register_program("train", "step", sig=(i,))
        if i < perf.PROGRAM_CAP:
            assert h is not None
    assert reg.register_program("train", "step", sig=("over",)) is None
    # existing signatures still resolve to their handle
    assert reg.register_program("train", "step", sig=(0,)) is not None


def test_program_scope_disambiguates_owners():
    """Two engines/models with the same (kind, sig) but different
    networks are different programs: the scope token keeps one
    owner's FLOPs from being read off a sibling's cache entry."""
    reg = perf.instance()
    h1 = reg.register_program("llm", "decode_step", scope="a")
    h2 = reg.register_program("llm", "decode_step", scope="b")
    assert h1 is not h2
    assert reg.register_program("llm", "decode_step", scope="a") is h1
    assert reg.get_program("llm", "decode_step", scope="b") is h2


def test_perfz_payload_never_relowers():
    """Repeated /perfz pulls must not trace again: the lowering thunk
    runs at most once per program (acceptance: lookups never
    re-lower)."""
    import jax
    calls = {"n": 0}

    def lower():
        calls["n"] += 1
        return jax.jit(lambda x: x * 2).lower(
            jax.ShapeDtypeStruct((8,), np.float32))

    reg = perf.instance()
    # a kind no hot path uses: the cost cache is process-wide and
    # survives perf.reset(), so this test must own its key outright
    h = reg.register_program("llm", "relower_probe", lower=lower,
                             scope="test")
    h.record(0.001)
    for _ in range(3):
        reg.payload()
    assert calls["n"] == 1


def _probe_lower(shape=(16, 16)):
    import jax
    return lambda: jax.jit(lambda x: x @ x).lower(
        jax.ShapeDtypeStruct(shape, np.float32))


def test_rates_hold_last_value_while_idle():
    """Documented semantics: an idle process HOLDS its last windowed
    rates instead of decaying to zero — a replica going quiet must
    not drag fleet_mfu down as if its roofline vanished."""
    reg = perf.instance()
    h = reg.register_program("llm", "idle_probe", lower=_probe_lower(),
                             scope="t")
    h.record(0.01)             # cost resolved at registration
    r1 = reg.rates()
    assert r1["mfu"] > 0
    with reg._mu:          # simulate the 60 s window expiring
        reg._buckets.clear()
    assert reg.rates() == r1


def test_failed_cost_busy_time_excluded_from_mfu():
    """A program whose backend reported no cost analysis must not
    enter the MFU denominator as zero-FLOP busy time (documented:
    excluded, visibly — not folded in)."""
    reg = perf.instance()
    good = reg.register_program("llm", "good", lower=_probe_lower(),
                                scope="t")
    good.record(1.0)
    mfu_before = reg.rates()["mfu"]
    assert mfu_before > 0
    bad = reg.register_program(
        "llm", "bad", scope="t",
        lower=lambda: (_ for _ in ()).throw(RuntimeError("none")))
    bad.record(10.0)       # 10x the busy time, zero counted FLOPs
    assert bad.cost_failed
    assert reg.rates()["mfu"] == pytest.approx(mfu_before), \
        "uncosted busy seconds deflated MFU"


def test_compile_attribution_survives_recompile_guard_optout():
    """FLAGS.recompile_warn_threshold=0 opts out of the recompile
    WARNING — perf must still split each signature's first (compiling)
    dispatch out of its MFU accounting via its own freshness
    tracking."""
    flags.set_flags({"recompile_warn_threshold": 0})
    try:
        model = _tiny_model()
        x = np.random.RandomState(0).randn(8, 8).astype(np.float32)
        y = np.random.RandomState(1).randint(0, 2, (8, 1))
        model.train_batch([x], [y])      # compile
        model.train_batch([x], [y])      # dispatch
        model.train_batch([x], [y])      # dispatch
        progs = [p.to_dict() for p in perf.instance().programs()
                 if p.kind == "step"]
        assert progs and progs[0]["dispatches"] == 2, progs
        ph = perf.instance().breakdown()["train"]["phases"]
        assert ph.get("compile", 0) > 0
    finally:
        flags.set_flags({"recompile_warn_threshold": 8})


def test_discarded_model_releases_registry_entries():
    """A sweep process building a Model per config must not fill
    PROGRAM_CAP with dead entries: GC of an unreferenced Model
    releases its scope (weakref.finalize backstop)."""
    import gc
    model = _tiny_model()
    x = np.random.RandomState(0).randn(8, 8).astype(np.float32)
    y = np.random.RandomState(1).randint(0, 2, (8, 1))
    model.train_batch([x], [y])
    scope = model._perf_scope
    reg = perf.instance()
    assert any(h.scope == scope for h in reg.programs())
    del model
    gc.collect()
    assert not any(h.scope == scope for h in reg.programs()), \
        "collected Model left perf-registry entries behind"


def test_finalizer_inside_a_locked_registration_does_not_deadlock():
    """``register_program`` builds its handle under the registry's
    (non-reentrant) lock; an allocation there can trigger a collection, and
    a finalizer that took the lock again hung a whole tier-1 run (PR 38,
    ``test_program_cap_discipline`` after an engine's owner had died in a
    cycle). The finalizer takes no lock: the scope goes at the next
    registration or listing."""
    import gc
    import threading

    class Owner:
        pass

    class CollectingLock:
        """The registry's own lock, forcing a collection inside every
        section it guards: what an unlucky allocation does, made certain."""

        def __init__(self, inner):
            self.inner = inner

        def __enter__(self):
            self.inner.acquire()
            gc.collect()

        def __exit__(self, *exc):
            self.inner.release()

    reg = perf.instance()
    owner, dead = Owner(), perf.next_scope()
    assert reg.register_program("llm", "decode_step", scope=dead) is not None
    perf.finalize_scope(owner, dead)
    gc.disable()
    try:
        owner.me = owner            # a cycle: only the collector frees it
        del owner
        reg._mu = CollectingLock(reg._mu)
        t = threading.Thread(
            target=lambda: reg.register_program("llm", "decode_step",
                                                scope="live"), daemon=True)
        t.start()
        t.join(timeout=30)
        assert not t.is_alive(), "finalizer deadlocked on the registry lock"
    finally:
        gc.enable()
    assert {h.scope for h in reg.programs()} == {"live"}


def test_prepare_resets_perf_programs():
    """Re-prepare rebuilds the compiled step (different optimizer →
    different FLOPs): the new program must not accumulate under the
    old program's cached cost entry."""
    model = _tiny_model()
    x = np.random.RandomState(0).randn(8, 8).astype(np.float32)
    y = np.random.RandomState(1).randint(0, 2, (8, 1))
    model.train_batch([x], [y])
    scope1 = model._perf_scope
    assert model._perf_programs
    model.prepare(optimizer=pt.optimizer.SGD(
        learning_rate=0.01, parameters=model.network),
        loss=nn.CrossEntropyLoss())
    assert model._perf_programs == {}
    assert model._perf_scope != scope1


# ---------------------------------------------------------------------------
# Model.fit — live MFU + breakdown over HTTP
# ---------------------------------------------------------------------------

def _tiny_model():
    pt.seed(0)
    net = nn.Sequential(nn.Linear(8, 16), nn.ReLU(), nn.Linear(16, 2))
    model = pt.Model(net)
    model.prepare(optimizer=pt.optimizer.SGD(learning_rate=0.1,
                                             parameters=net),
                  loss=nn.CrossEntropyLoss())
    return model


def test_model_fit_perfz_live_mfu_and_breakdown():
    from paddle_tpu.io import TensorDataset
    model = _tiny_model()
    x = np.random.RandomState(0).randn(64, 8).astype(np.float32)
    y = np.random.RandomState(1).randint(0, 2, (64, 1))
    # the metric registry is process-wide (other tests' fit runs share
    # the histogram); the breakdown comparison uses this test's delta
    hist0 = default_registry().get("train_loop_dispatch_seconds")
    hist0_sum = hist0.sum if hist0 is not None else 0.0
    model.fit(TensorDataset([x, y]), batch_size=16, epochs=2,
              verbose=0, steps_per_loop=2)

    srv = debug_server.DebugServer(port=0).start()
    try:
        base = f"http://127.0.0.1:{srv.port}"
        pz = _get_json(base, "/perfz")
        assert pz["enabled"]
        assert pz["mfu"] > 0
        assert pz["flops_per_second"] > 0
        assert pz["peaks"]["flops"] > 0 and pz["peaks"]["source"]
        loops = [p for p in pz["programs"]
                 if p["component"] == "train" and p["kind"] == "loop"]
        assert loops, pz["programs"]
        assert loops[0]["steps_per_dispatch"] == 2
        assert loops[0]["cost_resolved"] and loops[0]["flops"] > 0
        assert loops[0]["dispatches"] > 0
        # breakdown phases reproduce the dispatch histogram (same dt
        # values, compile split out) — "phases sum ≈ step time"
        ph = pz["breakdown"]["train"]["phases"]
        assert ph.get("dispatch", 0) > 0
        hist = default_registry().get("train_loop_dispatch_seconds")
        hist_delta = hist.sum - hist0_sum
        total = ph.get("dispatch", 0.0) + ph.get("compile", 0.0)
        assert hist_delta > 0 and \
            abs(total - hist_delta) / hist_delta < 0.05
        # /statusz carries the summary row; /metrics the gauges
        st = _get_json(base, "/statusz")
        assert st["perf"]["enabled"] and st["perf"]["programs"] >= 1
        with urllib.request.urlopen(base + "/metrics",
                                    timeout=30) as r:
            text = r.read().decode()
        assert "perf_mfu" in text and "perf_flops_per_second" in text
    finally:
        srv.stop()


def test_perf_disabled_records_nothing():
    model = _tiny_model()
    x = np.random.RandomState(0).randn(8, 8).astype(np.float32)
    y = np.random.RandomState(1).randint(0, 2, (8, 1))
    perf.disable()
    try:
        model.train_batch([x], [y])
        model.train_batch([x], [y])
        assert perf.instance().programs() == []
        assert perf.instance().breakdown() == {}
        assert model._perf_programs == {}
    finally:
        perf.enable()


# ---------------------------------------------------------------------------
# LLMEngine decode slab — live MFU, breakdown, served FLOPs
# ---------------------------------------------------------------------------

def _tiny_engine(decode_ticks=4, **kw):
    from paddle_tpu.inference.llm import LLMEngine
    from paddle_tpu.models.gpt import GPTForCausalLM, gpt_config
    pt.seed(0)
    cfg = gpt_config("gpt2-small", num_layers=2, hidden_size=64,
                     num_heads=4, vocab_size=97,
                     max_position_embeddings=128,
                     hidden_dropout=0.0, attention_dropout=0.0)
    net = GPTForCausalLM(cfg)
    return LLMEngine(net, max_seqs=4, page_size=8, num_pages=32,
                     max_len=64, prefill_chunk=8,
                     decode_ticks_per_dispatch=decode_ticks, **kw)


def test_engine_slab_perfz_and_served_flops():
    rng = np.random.RandomState(0)
    prompts = [rng.randint(0, 97, 8).tolist() for _ in range(3)]
    with _tiny_engine(decode_ticks=4) as eng:
        fpt = eng.flops_per_token
        assert fpt > 0
        futs = [eng.submit(p, max_new_tokens=16, tenant="gold")
                for p in prompts]
        outs = [f.result(timeout=240) for f in futs]
        # /perfz while live: close() removes the engine's program
        # entries from the registry (PROGRAM_CAP hygiene)
        pz = perf.perfz_payload()
    assert perf.instance().get_program(
        "llm", "decode_loop", (4,), scope=eng._perf_scope) is None, \
        "closed engine left program entries in the registry"
    # per-request attribution: analytic marginal cost of the computed
    # tokens, returned on the result and counted per tenant
    for o in outs:
        assert o["served_flops"] == fpt * (
            len(o["prompt_ids"]) + len(o["output_ids"]))
    fam = default_registry().get("llm_served_flops_total")
    got = fam.labels("gold").value
    assert got == sum(o["served_flops"] for o in outs)
    assert pz["mfu"] > 0
    slabs = [p for p in pz["programs"]
             if p["component"] == "llm" and p["kind"] == "decode_loop"]
    assert slabs and slabs[0]["sig"] == [4]
    assert slabs[0]["steps_per_dispatch"] == 4
    assert any(p["cost_resolved"] and p["flops"] > 0 for p in slabs)
    ph = pz["breakdown"]["llm"]["phases"]
    assert ph.get("decode", 0) > 0


def test_engine_perf_disabled_one_flag_check():
    rng = np.random.RandomState(0)
    perf.disable()
    try:
        with _tiny_engine(decode_ticks=4) as eng:
            eng.generate([rng.randint(0, 97, 8).tolist()],
                         max_new_tokens=8)
            assert eng._perf_programs == {}
        assert perf.instance().programs() == []
    finally:
        perf.enable()


def test_warming_process_exports_no_perf_gauges():
    """A registry that has never completed costed work must not SET
    the perf gauges: a warming replica's /metrics prescrape would
    otherwise export perf_mfu=0.0 and drag the fleet_mfu mean down —
    it must stay a hole (absent family) until real work lands."""
    reg = default_registry()
    reg.gauge("perf_mfu", "").set(0.7)   # value from earlier real work
    r = perf.instance().update_gauges()  # fresh registry, no work yet
    assert r["mfu"] == 0.0
    assert reg.get("perf_mfu").value == 0.7, \
        "never-worked registry stomped the gauge with 0.0"


def test_perf_attribute_idle_gap_consumes_compile_skip():
    """A record drained across an idle gap (unmeasurable interval) must
    still CONSUME its program's compile-skip marker — carried into a
    later record, it would book a real dispatch interval as compile time
    and keep it out of the program's accounting."""
    import time as _time
    with _tiny_engine(decode_ticks=1) as eng:
        eng._last_fetch_t = None
        eng._perf_attribute("M", 1, 1)
        assert ("mixed_tick", 1) in eng._perf_skipped
        h = perf.instance().register_program(
            "llm", "mixed_tick", sig=(1,), lower=_probe_lower(),
            scope=eng._perf_scope)
        eng._perf_programs[("mixed_tick", 1)] = h
        eng._last_fetch_t = _time.monotonic() - 0.01
        eng._perf_attribute("M", 1, 3)
        assert (h.dispatches, h.tokens) == (1, 3), \
            "the measured interval after the gap is the program's"
        assert h.seconds >= 0.01


def test_served_flops_excludes_cached_prefix_tokens():
    """The cost denominator charges COMPUTED tokens: a prefix-cache
    hit serves pages without recomputing them, and the second
    request's served_flops must be lower by exactly the reused
    tokens."""
    rng = np.random.RandomState(0)
    prefix = rng.randint(0, 97, 16).tolist()
    p1 = prefix + rng.randint(0, 97, 8).tolist()
    p2 = prefix + rng.randint(0, 97, 8).tolist()
    with _tiny_engine(decode_ticks=1, prefix_cache=True) as eng:
        fpt = eng.flops_per_token
        o1 = eng.submit(p1, max_new_tokens=4).result(timeout=240)
        o2 = eng.submit(p2, max_new_tokens=4).result(timeout=240)
        cached = eng.n_cached_tokens
    assert cached > 0, "shared prefix produced no cache hits"
    assert o1["served_flops"] == fpt * (len(p1) + len(o1["output_ids"]))
    assert o2["served_flops"] == fpt * (
        len(p2) - cached + len(o2["output_ids"]))


# ---------------------------------------------------------------------------
# analytic FLOPs vs XLA-counted FLOPs (parity pin, satellite 3)
# ---------------------------------------------------------------------------

def test_flops_parity_transformer_block():
    """Pin the analytic FLOPs path (``pt.flops``: per-layer formulas,
    the same multiply-add convention as the planner/test_summary_flops)
    against XLA's counted FLOPs for ONE transformer encoder block,
    read through the perf cost registry.

    Documented tolerance: the analytic count covers the Linear
    projections + norms only; XLA additionally counts the attention
    score/value matmuls (≈ s/(3·d_model) of the projection FLOPs at
    seq s), softmax/GELU elementwise work, and fuses some of it away.
    At s=32, d_model=128 that bounds the gap well inside ±25%, which
    is the pin — a broken analytic formula (dropped 2x, missing
    layer) lands far outside it."""
    import jax

    pt.seed(0)
    s, d = 32, 128
    net = nn.TransformerEncoderLayer(d_model=d, nhead=4,
                                     dim_feedforward=4 * d,
                                     dropout=0.0)
    net.eval()
    analytic = pt.flops(net, (1, s, d))
    assert analytic > 0

    from paddle_tpu.nn.layer import functional_call, split_state
    params, buffers = split_state(net)

    def fwd(p, b, x):
        out, _ = functional_call(net, p, b, x, training=False)
        return out

    x = np.zeros((1, s, d), np.float32)
    jitted = jax.jit(fwd)
    h = perf.register_program(
        "train", "block_fwd",
        lower=perf.make_lower(jitted, (params, buffers, x)))
    h.record(0.001)
    assert h.cost_resolved, "XLA cost analysis unavailable on CPU?"
    xla = h.flops
    ratio = analytic / xla
    assert 0.75 <= ratio <= 1.25, (
        f"analytic {analytic:.3g} vs XLA {xla:.3g} "
        f"(ratio {ratio:.3f}) — outside the documented ±25% band")


# ---------------------------------------------------------------------------
# fleet federation: down replica is a hole; failover attribution
# ---------------------------------------------------------------------------

def _prom(mfu=None, completed=1.0, fps=None):
    lines = ["# TYPE llm_requests_completed counter",
             f"llm_requests_completed {completed}"]
    if mfu is not None:
        lines += ["# TYPE perf_mfu gauge", f"perf_mfu {mfu}"]
    if fps is not None:
        lines += ["# TYPE perf_flops_per_second gauge",
                  f"perf_flops_per_second {fps}"]
    return "\n".join(lines) + "\n"


def test_fleet_mfu_down_replica_is_hole():
    from paddle_tpu.observability.metrics import MetricRegistry
    from paddle_tpu.serving.fleet import FleetScraper

    reg = MetricRegistry()
    sc = FleetScraper(registry=reg)
    sc.record("r0", _prom(mfu=0.4, fps=100.0))
    sc.record("r1", _prom(mfu=0.2, fps=50.0))
    agg = sc.aggregates()
    assert agg["mfu"] == pytest.approx(0.3)
    assert agg["mfu_replicas"] == 2
    assert agg["flops_per_second"] == pytest.approx(150.0)

    # r1 dies: its 0.2 must leave the mean entirely (a hole), not be
    # averaged in as 0.0 (which would read as "idle capacity")
    sc.record("r1", None)
    agg = sc.aggregates()
    assert agg["mfu"] == pytest.approx(0.4), \
        "down replica folded into fleet_mfu as a zero"
    assert agg["mfu_replicas"] == 1
    assert reg.get("fleet_mfu").value == pytest.approx(0.4)
    assert reg.get("fleet_replica_up").labels("r1").value == 0

    # a replica that exports no perf series at all is also a hole
    sc.record("r2", _prom(mfu=None))
    agg = sc.aggregates()
    assert agg["mfu"] == pytest.approx(0.4)
    assert agg["mfu_replicas"] == 1

    # nobody reports: mfu is None (unknown), not a fake zero
    sc.record("r0", None)
    agg = sc.aggregates()
    assert agg["mfu"] is None and agg["mfu_replicas"] == 0


def test_fleet_federates_perf_series():
    from paddle_tpu.observability.metrics import MetricRegistry
    from paddle_tpu.serving.fleet import FleetScraper

    sc = FleetScraper(registry=MetricRegistry())
    sc.record("r0", _prom(mfu=0.31))
    text = sc.render_prometheus()
    assert 'fleet_perf_mfu{replica="r0"} 0.31' in text


class _CrashOnceReplica:
    """First dispatch dies like a SIGKILLed sibling (ReplicaUnavailable
    before the engine sees the request — a real crash takes its
    process, and its counters, with it); later dispatches pass
    through. The router's nonce-pinned failover then re-runs the
    request on the healthy replica."""

    def __init__(self, inner):
        self.inner = inner
        self.crashed = False

    def submit(self, prompt_ids, **kw):
        from paddle_tpu.serving.replica import ReplicaUnavailable
        if not self.crashed:
            self.crashed = True
            raise ReplicaUnavailable("replica crashed mid-dispatch")
        return self.inner.submit(prompt_ids, **kw)

    def health(self):
        return self.inner.health()

    def cancel(self, request_id, **kw):
        return self.inner.cancel(request_id)

    def close(self):
        pass


def test_served_flops_failover_no_double_count():
    from paddle_tpu.serving import LocalReplica, Router

    fam = default_registry().get("llm_served_flops_total")
    base = fam.labels("gold").value if fam is not None else 0.0
    rng = np.random.RandomState(0)
    prompts = [rng.randint(0, 97, 8).tolist() for _ in range(2)]
    with _tiny_engine(decode_ticks=1) as eng:
        flaky = _CrashOnceReplica(LocalReplica(eng))
        healthy = LocalReplica(eng)
        router = Router({"r0": flaky, "r1": healthy},
                        policy="round_robin",
                        health_poll_interval=5.0, failover_budget=2)
        try:
            # two submissions: round-robin touches both seats, so the
            # flaky replica's crash-and-failover path runs regardless
            # of which seat goes first
            outs = [router.submit(p, max_new_tokens=8,
                                  tenant="gold").result(timeout=240)
                    for p in prompts]
        finally:
            router.close()
    assert flaky.crashed, "the crash path never ran"
    assert all(o["output_ids"] and o.get("served_flops", 0) > 0
               for o in outs)
    got = default_registry().get(
        "llm_served_flops_total").labels("gold").value - base
    # exactly the finished requests' worth: the crashed dispatch never
    # reached a finish, so each failover re-run is the only
    # attribution for its request
    assert got == pytest.approx(sum(o["served_flops"] for o in outs)), \
        f"failover double-counted served FLOPs: {got}"
