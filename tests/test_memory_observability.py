"""HBM attribution ledger (observability/memory.py, ISSUE 14): owners
register attributed reservations at allocation boundaries, every read
reconciles against device.memory_stats() with an explicit unattributed
residual, the engine's KV-pool split tracks the page table EXACTLY,
and RESOURCE_EXHAUSTED anywhere produces a flight dump carrying the
per-owner table — an OOM is a diffable accounting, not a stack trace.
"""

import json
import os
import subprocess
import sys
import time
import urllib.request

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import nn
from paddle_tpu.core import flags as _flags
from paddle_tpu.observability import memory as memobs
from paddle_tpu.observability import tracing
from paddle_tpu.observability.metrics import default_registry

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _fresh_ledger():
    """Process-global singleton isolation: every test gets a fresh
    ledger and a clean mem_* gauge namespace."""
    memobs.reset()
    was = memobs.enabled()
    memobs.enable()
    reg = default_registry()
    for fam in ("mem_bytes", "mem_watermark_bytes",
                "mem_headroom_pages", "host_rss_bytes"):
        reg.unregister(fam)
    yield
    memobs.reset()
    (memobs.enable if was else memobs.disable)()


def tiny_gpt():
    from paddle_tpu.models.gpt import GPTForCausalLM, gpt_config
    pt.seed(0)
    cfg = gpt_config("gpt2-small", num_layers=2, hidden_size=64,
                     num_heads=4, vocab_size=97,
                     max_position_embeddings=96, hidden_dropout=0.0,
                     attention_dropout=0.0)
    return GPTForCausalLM(cfg)


def kv_rows(led=None):
    led = led or memobs.instance()
    return {r["kind"]: r["bytes"] for r in led.rows()
            if r["owner"] == "kv_pool"}


# ---------------------------------------------------------------------------
# ledger core
# ---------------------------------------------------------------------------


def test_tree_bytes_by_dtype_abstract():
    tree = {"a": np.zeros((4, 8), np.float32),
            "b": np.zeros((16,), np.int8),
            "c": {"d": np.zeros((2, 2), np.float32)},
            "e": "not-an-array"}
    out = memobs.tree_bytes_by_dtype(tree)
    assert out == {"float32": 4 * 8 * 4 + 2 * 2 * 4, "int8": 16}


def test_reconciliation_residual_is_the_closing_line(monkeypatch):
    """The acceptance pin: sum(attributed device bytes) +
    unattributed residual == device bytes_in_use, exactly; host rows
    stay OUT of the device reconciliation."""
    led = memobs.MemoryLedger()
    led.set_entry("s0", "params", "float32", 1000)
    led.set_entry("s0", "kv_pool", "free", 2000)
    led.set_entry("s0", "staging", "host", 777, placement="host")
    monkeypatch.setattr(
        memobs, "_collect_device_stats",
        lambda: {"bytes_in_use": 5000.0, "bytes_limit": 10000.0,
                 "peak_bytes_in_use": 6000.0, "devices": 1})
    p = led.payload()
    assert p["attributed_device_bytes"] == 3000
    assert p["attributed_host_bytes"] == 777
    assert p["unattributed_bytes"] == 2000
    assert p["attributed_device_bytes"] + p["unattributed_bytes"] \
        == p["device"]["bytes_in_use"]
    assert "fragmentation" in p["unattributed_note"]


def test_no_device_stats_is_a_hole_not_zero():
    """CPU backends: the residual is explicit None + note, never a
    fabricated 0 (which would read as 'perfectly attributed')."""
    led = memobs.MemoryLedger()
    led.set_entry("s0", "params", "float32", 1000)
    p = led.payload()       # real CPU backend: no memory_stats
    assert "unattributed_bytes" in p
    assert p["unattributed_bytes"] is None
    assert "memory_stats" in p["unattributed_note"]
    assert p["host_rss_bytes"] is None or p["host_rss_bytes"] > 0


def test_inactive_ledger_never_queries_devices(monkeypatch):
    """A router-only process (no registered device rows) answering
    /memz must not initialize a jax backend."""
    led = memobs.MemoryLedger()

    def boom():
        raise AssertionError("device query from an inactive ledger")

    monkeypatch.setattr(memobs, "_collect_device_stats", boom)
    assert led.payload()["device"] is None
    led.set_entry("s0", "staging", "host", 10, placement="host")
    assert led.payload()["device"] is None   # host rows don't activate


def test_provider_rows_live_and_self_unregister():
    led = memobs.MemoryLedger()
    state = {"n": 1, "alive": True}

    def prov():
        if not state["alive"]:
            return None
        return {"rows": [{"owner": "pool", "kind": "free",
                          "bytes": state["n"] * 100.0}],
                "headroom_pages": state["n"], "page_bytes": 100.0}

    led.register_provider("s1", prov)
    assert led.rows()[0]["bytes"] == 100.0
    state["n"] = 3      # LIVE: the read recomputes, no re-registration
    assert led.rows()[0]["bytes"] == 300.0
    assert led.headroom()["kv_pages_addable"] == 3
    state["alive"] = False
    assert led.rows() == [] and led.headroom() is None
    state["alive"] = True   # dead providers stay unregistered
    assert led.rows() == []


def test_remove_scope_drops_entries_and_provider():
    led = memobs.MemoryLedger()
    led.set_entry("s1", "a", "k", 1)
    led.set_entry("s2", "b", "k", 2)
    led.register_provider("s1", lambda: {"rows": []})
    assert led.remove_scope("s1") == 2
    assert [r["owner"] for r in led.rows()] == ["b"]


def test_watermarks_tagged_by_active_span_and_peak_rows():
    led = memobs.MemoryLedger()
    tracing.enable()
    try:
        led.set_entry("s0", "params", "float32", 1000)
        with tracing.span("train.dispatch"):
            led.payload()
        led.set_entry("s0", "params", "float32", 5000)
        with tracing.span("llm.decode"):
            p = led.payload()
    finally:
        tracing.disable()
    assert p["watermarks"]["train.dispatch"]["bytes"] == 1000
    assert p["watermarks"]["llm.decode"]["bytes"] == 5000
    assert led.watermark_bytes() == 5000
    # delta-since-watermark baselines on the peak's row snapshot
    led.set_entry("s0", "params", "float32", 4000)
    led.set_entry("s0", "kv_pool", "free", 250)
    delta = led._delta_since_watermark(led.rows())
    assert {(d["owner"], d["delta_bytes"]) for d in delta} == \
        {("params", -1000.0), ("kv_pool", 250.0)}


def test_near_oom_one_shot_flight_dump(tmp_path, monkeypatch):
    from paddle_tpu.observability import flight
    rec = flight.install_flight_recorder(str(tmp_path))
    try:
        led = memobs.MemoryLedger()
        led.set_entry("s0", "params", "float32", 9500)
        monkeypatch.setattr(
            memobs, "_collect_device_stats",
            lambda: {"bytes_in_use": 9500.0, "bytes_limit": 10000.0,
                     "peak_bytes_in_use": 9500.0, "devices": 1})
        led.payload()
        dumps = [f for f in os.listdir(tmp_path) if "near_oom" in f]
        assert len(dumps) == 1, dumps
        rows = [json.loads(ln) for ln in open(tmp_path / dumps[0])]
        extra = next(r for r in rows if r.get("kind") == "extra")
        assert extra["used_fraction"] >= 0.9
        assert extra["memz"]["attributed_device_bytes"] == 9500
        led.payload()       # one-shot: a second crossing stays quiet
        assert len([f for f in os.listdir(tmp_path)
                    if "near_oom" in f]) == 1
        led.reset_one_shots()
        led.payload()       # re-armed (the dedupe-less dump path
        # overwrites the same file — still exactly one on disk)
        assert len([f for f in os.listdir(tmp_path)
                    if "near_oom" in f]) == 1
    finally:
        rec.uninstall()


def test_near_oom_arms_at_metrics_prescrape_too(tmp_path, monkeypatch):
    """update_gauges (the /metrics prescrape path) is a ledger read:
    crossing the threshold there must arm the snapshot — a replica
    scraped only via /metrics still gets its pre-crash baseline."""
    from paddle_tpu.observability import flight
    rec = flight.install_flight_recorder(str(tmp_path))
    try:
        led = memobs.MemoryLedger()
        led.set_entry("s0", "params", "float32", 9800)
        monkeypatch.setattr(
            memobs, "_collect_device_stats",
            lambda: {"bytes_in_use": 9800.0, "bytes_limit": 10000.0,
                     "peak_bytes_in_use": 9800.0, "devices": 1})
        led.update_gauges()
        assert [f for f in os.listdir(tmp_path) if "near_oom" in f]
    finally:
        rec.uninstall()


def test_headroom_mixed_page_sizes_bytes_exact():
    """Two pools with different page_bytes: the byte estimate stays
    exact (per-provider pages x its page size), page-denominated
    fields go None instead of lying in the larger pool's units."""
    led = memobs.MemoryLedger()
    led.register_provider("a", lambda: {
        "rows": [], "headroom_pages": 100, "page_bytes": 1024.0})
    led.register_provider("b", lambda: {
        "rows": [], "headroom_pages": 10, "page_bytes": 4096.0})
    h = led.headroom()
    assert h["kv_pages_addable"] == 110
    assert h["bytes_addable"] == 100 * 1024 + 10 * 4096
    assert h["page_bytes"] is None
    led.remove_scope("b")
    h = led.headroom()
    assert h["page_bytes"] == 1024.0 and h["bytes_addable"] == 102400


def test_is_oom_matching():
    assert memobs.is_oom(RuntimeError(
        "RESOURCE_EXHAUSTED: Out of memory allocating 2.5G"))
    assert memobs.is_oom(MemoryError("out of memory"))
    assert not memobs.is_oom(ValueError("shapes mismatch"))


def test_maybe_dump_oom_carries_table_and_is_one_shot(tmp_path):
    from paddle_tpu.observability import flight
    rec = flight.install_flight_recorder(str(tmp_path))
    try:
        memobs.set_entry("s0", "kv_pool", "free", 4096)
        exc = RuntimeError("RESOURCE_EXHAUSTED: failed to allocate")
        path = memobs.maybe_dump_oom(exc, component="llm")
        assert path and os.path.exists(path)
        rows = [json.loads(ln) for ln in open(path)]
        assert rows[0]["reason"] == "oom"
        extra = next(r for r in rows if r.get("kind") == "extra")
        assert extra["component"] == "llm"
        assert any(r["owner"] == "kv_pool"
                   for r in extra["memz"]["owners"])
        assert "delta_since_watermark" in extra
        # one dump per process; non-OOMs never dump
        assert memobs.maybe_dump_oom(exc) is None
        assert memobs.maybe_dump_oom(ValueError("x")) is None
    finally:
        rec.uninstall()


def test_oom_one_shot_not_consumed_without_recorder(tmp_path):
    """A recorder-less process hitting an OOM must NOT burn the
    one-shot: once a recorder is installed, the NEXT OOM still
    produces the forensic dump (same for the near-OOM latch)."""
    from paddle_tpu.observability import flight
    assert flight.get_flight_recorder() is None
    exc = RuntimeError("RESOURCE_EXHAUSTED: allocation failed")
    memobs.set_entry("s0", "kv_pool", "free", 64)
    assert memobs.maybe_dump_oom(exc) is None        # no recorder yet
    rec = flight.install_flight_recorder(str(tmp_path))
    try:
        path = memobs.maybe_dump_oom(exc)            # still armed
        assert path and os.path.exists(path)
    finally:
        rec.uninstall()


def test_disabled_is_one_flag_check(tmp_path):
    from paddle_tpu.observability import flight
    rec = flight.install_flight_recorder(str(tmp_path))
    try:
        memobs.disable()
        assert memobs.maybe_dump_oom(
            RuntimeError("RESOURCE_EXHAUSTED")) is None
        assert not os.listdir(tmp_path)
        # a disabled engine registers nothing
        from paddle_tpu.inference.llm import LLMEngine
        with LLMEngine(tiny_gpt(), max_seqs=2, page_size=4,
                       num_pages=16, prefill_chunk=8) as eng:
            assert memobs.instance().rows() == []
            assert memobs.instance().headroom() is None
            del eng
    finally:
        memobs.enable()
        rec.uninstall()


# ---------------------------------------------------------------------------
# engine: attribution vs pool accounting, exactly
# ---------------------------------------------------------------------------


def test_engine_kv_attribution_tracks_page_table_exactly():
    """Ledger kv rows == page-table math across the cache lifecycle:
    admit (shared map + private suffix), divergence (page-granular
    CoW: a mid-page divergent prompt computes a private copy), cancel
    (pages reclaimed at the boundary), eviction (refcount-zero LRU
    residents reclaimed under pressure = headroom, counted once)."""
    from paddle_tpu.inference.llm import LLMEngine
    net = tiny_gpt()
    rng = np.random.RandomState(0)
    base = rng.randint(0, 97, 8).tolist()       # 2 full pages of 4
    led = memobs.instance()
    with LLMEngine(net, max_seqs=4, page_size=4, num_pages=32,
                   prefill_chunk=16) as eng:
        usable = eng.num_pages - 1
        pb = eng._page_bytes

        def check():
            rows = kv_rows(led)
            free = len(eng._free_pages)
            shared = eng._cache.shared_page_count
            assert rows["free"] == free * pb
            assert rows["prefix_shared"] == shared * pb
            assert rows["private"] == (usable - free - shared) * pb
            assert rows["scratch"] == pb
            assert sum(rows.values()) == eng.num_pages * pb
            h = led.headroom()
            assert h["kv_pages_addable"] == \
                free + eng._cache.evictable_count

        check()                                   # idle pool
        r1 = eng.submit(base, max_new_tokens=4).result(timeout=240)
        check()                                   # prompt pages shared
        assert eng._cache.shared_page_count == 2
        # admit a prefix-sharing sibling and a mid-page divergent
        # prompt (CoW at page granularity: it misses the second
        # page's digest and computes a private copy)
        divergent = list(base)
        divergent[6] = (divergent[6] + 1) % 97
        r2 = eng.submit(base + base[:3],
                        max_new_tokens=4).result(timeout=240)
        r3 = eng.submit(divergent, max_new_tokens=4).result(timeout=240)
        assert r1["output_ids"] and r2["output_ids"] and \
            r3["output_ids"]
        check()
        # cancel mid-generation: pages come back at the drain boundary
        f = eng.submit(rng.randint(0, 97, 8).tolist(),
                       max_new_tokens=64)
        eng.cancel(f.request_id)
        with pytest.raises(Exception):
            f.result(timeout=240)
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:
            if all(s is None for s in eng._slots):
                break
            time.sleep(0.01)
        check()
        # quiescent: everything not cached is free again
        rows = kv_rows(led)
        assert rows["private"] == 0, rows


def test_engine_close_removes_rows_and_unexports_headroom():
    from paddle_tpu.inference.llm import LLMEngine
    led = memobs.instance()
    eng = LLMEngine(tiny_gpt(), max_seqs=2, page_size=4, num_pages=16,
                    prefill_chunk=8, decode_ticks_per_dispatch=4)
    led.update_gauges()
    assert default_registry().get("mem_headroom_pages") is not None
    assert any(r["owner"] == "decode_carry" for r in led.rows())
    eng.close()
    assert led.rows() == [] and led.headroom() is None
    led.update_gauges()
    # the family is GONE (a hole in federation), and stale mem_bytes
    # children are zeroed
    assert default_registry().get("mem_headroom_pages") is None
    fam = default_registry().get("mem_bytes")
    assert all(c.value == 0 for c in fam.children())


def test_forced_resource_exhausted_flight_dump_subprocess(tmp_path):
    """The OOM forensics acceptance, end to end in a real engine
    loop: a decode dispatch raising RESOURCE_EXHAUSTED produces a
    flight dump whose extra row carries the per-owner ledger table
    (kv_pool split included) — from a subprocess, like a real crash."""
    code = f"""
import jax; jax.config.update("jax_platforms", "cpu")
import numpy as np
import paddle_tpu as pt
from paddle_tpu.inference.llm import LLMEngine
from paddle_tpu.models.gpt import GPTForCausalLM, gpt_config
from paddle_tpu.observability import flight

flight.install_flight_recorder({str(tmp_path)!r})
pt.seed(0)
cfg = gpt_config("gpt2-small", num_layers=2, hidden_size=64,
                 num_heads=4, vocab_size=97,
                 max_position_embeddings=96, hidden_dropout=0.0,
                 attention_dropout=0.0)
eng = LLMEngine(GPTForCausalLM(cfg), max_seqs=2, page_size=4,
                num_pages=32, prefill_chunk=8)

def oom(*a, **kw):
    raise RuntimeError(
        "RESOURCE_EXHAUSTED: Out of memory while trying to allocate "
        "9663676416 bytes.")

eng._mixed_fn = oom
eng._decode_fn = oom
f = eng.submit(np.random.RandomState(0).randint(0, 97, 6).tolist(),
               max_new_tokens=4)
exc = None
try:
    f.result(timeout=240)
except Exception as e:
    exc = e
assert exc is not None and "RESOURCE_EXHAUSTED" in str(exc), exc
eng.close()
print("WORKER OK")
"""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    p = subprocess.run([sys.executable, "-c", code], env=env, cwd=REPO,
                       capture_output=True, text=True, timeout=55)
    assert p.returncode == 0 and "WORKER OK" in p.stdout, \
        (p.returncode, p.stdout[-500:], p.stderr[-2000:])
    dumps = [f for f in os.listdir(tmp_path) if "_oom" in f]
    assert dumps, os.listdir(tmp_path)
    rows = [json.loads(ln) for ln in open(tmp_path / dumps[0])]
    assert rows[0]["reason"] == "oom"
    extra = next(r for r in rows if r.get("kind") == "extra")
    assert extra["component"] == "llm"
    owners = {r["owner"] for r in extra["memz"]["owners"]}
    assert "kv_pool" in owners, owners
    assert "RESOURCE_EXHAUSTED" in extra["error"]


# ---------------------------------------------------------------------------
# model + checkpoint owners
# ---------------------------------------------------------------------------


def test_model_registers_params_buffers_opt_state_per_dtype():
    pt.seed(0)
    net = nn.Sequential(nn.Linear(8, 16), nn.ReLU(), nn.Linear(16, 2))
    model = pt.Model(net)
    model.prepare(
        optimizer=pt.optimizer.Adam(learning_rate=1e-3,
                                    parameters=net),
        loss=nn.CrossEntropyLoss())
    rows = {(r["owner"], r["kind"]): r["bytes"]
            for r in memobs.instance().rows()}
    n_param_bytes = (8 * 16 + 16 + 16 * 2 + 2) * 4
    assert rows[("train_params", "float32")] == n_param_bytes
    x = np.random.RandomState(0).randn(16, 8).astype(np.float32)
    y = np.random.RandomState(1).randint(0, 2, (16, 1))
    model.train_batch([x], [y])
    rows = {(r["owner"], r["kind"]): r["bytes"]
            for r in memobs.instance().rows()}
    # Adam: m + v per param (+ scalar step counters, dtype-dependent)
    assert rows[("train_opt_state", "float32")] >= 2 * n_param_bytes
    # re-prepare resets the scope: exactly one generation of rows
    model.prepare(
        optimizer=pt.optimizer.SGD(learning_rate=0.1, parameters=net),
        loss=nn.CrossEntropyLoss())
    rows2 = [r for r in memobs.instance().rows()
             if r["owner"] == "train_params"]
    assert len(rows2) == 1 and rows2[0]["bytes"] == n_param_bytes


def test_checkpoint_staging_registers_host_bytes(tmp_path):
    from paddle_tpu.io.checkpoint import CheckpointManager
    mgr = CheckpointManager(str(tmp_path), async_save=True)
    tree = {"w": np.ones((1000,), np.float32)}
    mgr.save(1, tree)
    row = next(r for r in memobs.instance().rows()
               if r["owner"] == "ckpt_staging")
    assert row["placement"] == "host" and row["bytes"] in (0.0, 4000.0)
    mgr.wait_until_finished()
    row = next(r for r in memobs.instance().rows()
               if r["owner"] == "ckpt_staging")
    assert row["bytes"] == 0.0
    p = memobs.instance().payload()
    assert p["attributed_host_bytes"] == 0.0


# ---------------------------------------------------------------------------
# HTTP surfaces
# ---------------------------------------------------------------------------


def _get_json(base, path):
    with urllib.request.urlopen(base + path, timeout=30) as r:
        return json.loads(r.read())


def test_memz_statusz_metrics_over_http(monkeypatch):
    from paddle_tpu.inference.llm import LLMEngine
    from paddle_tpu.observability import server as dbg
    srv = dbg.DebugServer(port=0).start()
    try:
        base = f"http://127.0.0.1:{srv.port}"
        with LLMEngine(tiny_gpt(), max_seqs=2, page_size=4,
                       num_pages=16, prefill_chunk=8) as eng:
            eng.generate([[1, 2, 3, 4, 5]], max_new_tokens=3)
            mz = _get_json(base, "/memz")
            assert mz["enabled"] is True
            kinds = {(r["owner"], r["kind"]) for r in mz["owners"]}
            assert ("kv_pool", "free") in kinds
            assert "unattributed_bytes" in mz
            assert mz["headroom"]["kv_pages_addable"] > 0
            assert mz["watermarks"]
            st = _get_json(base, "/statusz")
            assert st["memory"]["enabled"] is True
            assert st["memory"]["attributed_device_bytes"] > 0
            assert st["memory"]["kv_pages_addable"] > 0
            # CPU: device_memory must be the explicit fallback dict,
            # not a misleading {}
            assert st["device_memory"], st
            with urllib.request.urlopen(base + "/metrics",
                                        timeout=30) as r:
                text = r.read().decode()
            assert "mem_headroom_pages" in text
            assert 'mem_bytes{owner="kv_pool",kind="free"}' in text
            assert "mem_watermark_bytes" in text
    finally:
        srv.stop()


def test_statusz_device_memory_sample_cached_1s(monkeypatch):
    from paddle_tpu.observability import server as dbg
    calls = {"n": 0}

    def fake_sample(registry=None):
        calls["n"] += 1
        return {}

    monkeypatch.setattr(dbg, "sample_device_memory", fake_sample)
    srv = dbg.DebugServer(port=0).start()
    try:
        base = f"http://127.0.0.1:{srv.port}"
        for _ in range(5):          # a scrape storm
            st = _get_json(base, "/statusz")
        assert calls["n"] == 1, calls   # one sample per TTL window
        # and the CPU fallback replaced the empty dict
        assert "host_rss_bytes" in st["device_memory"], st
    finally:
        srv.stop()


def test_sample_device_memory_cpu_sets_host_rss_fallback():
    from paddle_tpu.observability.exporters import sample_device_memory
    out = sample_device_memory()
    assert out == {}                       # CPU: a hole, no device gauge
    fam = default_registry().get("device_memory_bytes")
    assert fam is None or not fam.children()
    rss = default_registry().get("host_rss_bytes")
    if memobs.host_rss_bytes() is not None:
        assert rss is not None and rss.value > 0


# ---------------------------------------------------------------------------
# fleet federation + bench ledger satellites
# ---------------------------------------------------------------------------


def test_fleet_headroom_federation_hole_semantics():
    """A replica that exports mem_headroom_pages enters the sum; one
    without the family (warming / no pool) and a down replica are
    HOLES — absent from sum AND denominator."""
    from paddle_tpu.serving.fleet import FleetScraper
    s = FleetScraper()
    s.record("r0", "# TYPE mem_headroom_pages gauge\n"
                   "mem_headroom_pages 40.0\n")
    s.record("r1", "# TYPE llm_tokens_generated counter\n"
                   "llm_tokens_generated 5\n")     # no pool yet
    s.record("r2", None)                           # down
    agg = s.aggregates()
    assert agg["mem_headroom_pages"] == 40.0
    assert agg["mem_headroom_replicas"] == 1
    reg = default_registry()
    assert reg.get("fleet_headroom_pages").value == 40.0
    assert reg.get("fleet_headroom_replicas").value == 1
    # nobody reports: sum is None (not 0-with-denominator)
    s.forget("r0")
    agg = s.aggregates()
    assert agg["mem_headroom_pages"] is None
    assert agg["mem_headroom_replicas"] == 0
    # per-replica federation rides the mem_ prefix
    s.record("r0", "# TYPE mem_headroom_pages gauge\n"
                   "mem_headroom_pages 12.0\n")
    text = s.render_prometheus()
    assert 'fleet_mem_headroom_pages{replica="r0"} 12.0' in text
    rep = s.replica_report()
    assert rep["r0"]["mem_headroom_pages"] == 12.0
    assert rep["r1"]["mem_headroom_pages"] is None


def test_bench_ledger_peak_mem_bytes_roundtrip(tmp_path, monkeypatch):
    sys.path.insert(0, os.path.join(REPO, "tools"))
    import bench_ledger as bl
    path = str(tmp_path / "ledger.jsonl")
    # old-schema row (no peak_mem_bytes key at all) + new row
    old = bl.make_row("llm_bench", "wl", 10.0, "tok/s", backend="cpu")
    old.pop("peak_mem_bytes")
    bl.append_row(old, path=path)
    new = bl.make_row("llm_bench", "wl", 11.0, "tok/s", backend="cpu",
                      peak_mem_bytes=123456.0)
    assert new["peak_mem_bytes"] == 123456.0
    bl.append_row(new, path=path)
    rows = bl.read_ledger(path)
    assert len(rows) == 2
    assert "peak_mem_bytes" not in rows[0]
    assert rows[1]["peak_mem_bytes"] == 123456.0
    # --compare tolerates the absent field on the old row
    verdicts = bl.compare(rows)
    assert len(verdicts) == 1
    assert verdicts[0]["newest_peak_mem_bytes"] == 123456.0
    assert verdicts[0]["status"] in ("ok", "regressed")
    # and a row with peak populated still passes required validation
    assert bl.ci_gate(path=path) in (0, 3)


def test_llm_bench_peak_helper_reads_watermark():
    sys.path.insert(0, os.path.join(REPO, "tools"))
    import llm_bench
    memobs.set_entry("s0", "kv_pool", "free", 8192)
    peak = llm_bench._peak_mem_bytes()
    assert peak == 8192
