"""Fleet observability (ISSUE 7 tentpoles 2+3 and satellites):
metrics federation (FleetScraper, /fleetz, replica-labeled re-export),
SLO burn-rate monitoring (SLOTracker, /sloz, breach latch on
/healthz), the /tracez query filters, and the trace_merge tool.

Stub replicas throughout — this is the control/observability plane,
no compiles needed.
"""

import json
import threading
import time
import urllib.request
from urllib.error import HTTPError
from urllib.request import Request, urlopen

import pytest

from paddle_tpu.observability import tracing
from paddle_tpu.observability.metrics import MetricRegistry
from paddle_tpu.observability.server import DebugServer
from paddle_tpu.observability.slo import SLOTracker
from paddle_tpu.serving import Router, SLOClass
from paddle_tpu.serving.fleet import FleetScraper, parse_prometheus_text

REPLICA_TEXT = """# HELP llm_tokens_generated tokens emitted
# TYPE llm_tokens_generated counter
llm_tokens_generated {tokens}
# TYPE llm_prompt_tokens counter
llm_prompt_tokens {prompt}
# TYPE llm_prefix_cache_hit_tokens counter
llm_prefix_cache_hit_tokens {hits}
# TYPE llm_requests_completed counter
llm_requests_completed {done}
# TYPE llm_kv_page_utilization gauge
llm_kv_page_utilization {kv}
# TYPE llm_batch_occupancy histogram
llm_batch_occupancy_bucket{{le="0.5"}} 1
llm_batch_occupancy_bucket{{le="+Inf"}} 2
llm_batch_occupancy_sum {occ_sum}
llm_batch_occupancy_count 2
"""


def replica_text(tokens=10, prompt=100, hits=40, done=3, kv=0.5,
                 occ_sum=1.0):
    return REPLICA_TEXT.format(tokens=tokens, prompt=prompt, hits=hits,
                               done=done, kv=kv, occ_sum=occ_sum)


# ---------------------------------------------------------------------------
# prometheus parsing
# ---------------------------------------------------------------------------

def test_parse_prometheus_text_families_and_labels():
    fams = parse_prometheus_text(replica_text())
    assert fams["llm_tokens_generated"]["type"] == "counter"
    assert fams["llm_tokens_generated"]["samples"] == [
        ("llm_tokens_generated", {}, 10.0)]
    occ = fams["llm_batch_occupancy"]
    assert occ["type"] == "histogram"
    names = [s[0] for s in occ["samples"]]
    assert "llm_batch_occupancy_sum" in names
    buckets = [s for s in occ["samples"]
               if s[0] == "llm_batch_occupancy_bucket"]
    assert buckets[0][1] == {"le": "0.5"}
    assert buckets[1][2] == 2.0    # +Inf parses


def test_parse_skips_garbage_lines():
    fams = parse_prometheus_text(
        "not a metric line at all\nx{y=unquoted} 1\nok_metric 3\n")
    assert fams["ok_metric"]["samples"] == [("ok_metric", {}, 3.0)]
    assert "x" not in fams


def test_parse_label_value_with_comma():
    fams = parse_prometheus_text('m{a="x,y",b="z"} 1\n')
    assert fams["m"]["samples"] == [("m", {"a": "x,y", "b": "z"}, 1.0)]


# ---------------------------------------------------------------------------
# FleetScraper
# ---------------------------------------------------------------------------

class ScrapableStub:
    def __init__(self, text):
        self.text = text

    def metrics_text(self):
        return self.text


def test_scraper_federates_with_replica_label():
    s = FleetScraper(registry=MetricRegistry())
    s.record("r0", replica_text(tokens=10))
    s.record("r1", replica_text(tokens=20))
    out = s.render_prometheus()
    assert 'fleet_llm_tokens_generated{replica="r0"} 10.0' in out
    assert 'fleet_llm_tokens_generated{replica="r1"} 20.0' in out
    # histogram labels merge after the replica label
    assert 'fleet_llm_batch_occupancy_bucket{replica="r0",le="0.5"} ' \
        in out
    assert "# TYPE fleet_llm_tokens_generated counter" in out


def test_scraper_aggregates_hit_rate_is_fleet_wide():
    reg = MetricRegistry()
    s = FleetScraper(registry=reg)
    s.record("r0", replica_text(prompt=100, hits=40))
    s.record("r1", replica_text(prompt=300, hits=20))
    agg = s.aggregates()
    # sum(hits)/sum(prompts), NOT the mean of per-replica rates
    assert agg["prefix_cache_hit_rate"] == pytest.approx(60 / 400)
    assert agg["replicas_scraped"] == 2
    assert agg["tokens_generated"] == 20.0
    assert agg["occupancy"] == pytest.approx(0.5)
    assert reg.get("fleet_prefix_cache_hit_rate").value == \
        pytest.approx(0.15)


def test_scraper_down_replica_drops_out_of_aggregates():
    reg = MetricRegistry()
    s = FleetScraper(registry=reg)
    s.record("r0", replica_text(tokens=10))
    s.record("r1", replica_text(tokens=20))
    s.record("r1", None)               # scrape failed
    agg = s.aggregates()
    assert agg["replicas_scraped"] == 1
    assert agg["tokens_generated"] == 10.0
    assert 'replica="r1"' not in s.render_prometheus()
    rep = s.replica_report()
    assert rep["r1"]["up"] is False    # marked down, not hidden
    assert rep["r0"]["up"] is True
    assert reg.get("fleet_replica_up").labels("r1").value == 0


def test_scraper_scrape_uses_client_surface_and_tolerates_absence():
    s = FleetScraper(registry=MetricRegistry())
    assert s.scrape("r0", ScrapableStub(replica_text())) is True
    # non-exporters (no surface / deliberate opt-out) stay ABSENT —
    # a healthy LocalReplica must not read as a down replica
    assert s.scrape("r1", object()) is False
    class OptOut:
        metrics_opt_out = True
        def metrics_text(self):
            return None
    assert s.scrape("r2", OptOut()) is False
    rep = s.replica_report()
    assert rep["r0"]["up"]
    assert "r1" not in rep and "r2" not in rep
    # an EXPORTER whose scrape fails IS down
    class Broken:
        def metrics_text(self):
            return None
    assert s.scrape("r3", Broken()) is False
    assert s.replica_report()["r3"]["up"] is False
    # mark_unreachable follows the same split
    s.mark_unreachable("r0", ScrapableStub(""))
    assert s.replica_report()["r0"]["up"] is False
    s.mark_unreachable("r2", OptOut())
    assert "r2" not in s.replica_report()


def test_scraper_forget_zeroes_liveness_of_past_exporter():
    reg = MetricRegistry()
    s = FleetScraper(registry=reg)
    s.record("r0", replica_text())
    assert reg.get("fleet_replica_up").labels("r0").value == 1
    s.forget("r0")
    assert reg.get("fleet_replica_up").labels("r0").value == 0
    assert s.aggregates()["replicas_scraped"] == 0


def test_slo_gauges_decay_via_refresh_and_report():
    t, clock = mk_tracker(targets={"gold": 0.9})
    for _ in range(5):
        t.record("gold", None, 0.01, "error")
    g = t.registry.get("slo_burn_rate")
    assert g.labels("gold", "short").value == pytest.approx(10.0)
    clock["t"] += 500.0                # everything ages out
    # no new traffic: refresh (the router poll) must decay the gauge
    t.refresh()
    assert g.labels("gold", "short").value == 0.0
    assert g.labels("gold", "long").value == 0.0
    # and reading /sloz republishes too (they can never disagree)
    for _ in range(2):
        t.record("gold", None, 0.01, "error")
    assert g.labels("gold", "short").value > 0
    clock["t"] += 500.0
    rep = t.report()
    assert rep["classes"]["gold"]["windows"]["short"]["burn_rate"] == 0
    assert g.labels("gold", "short").value == 0.0


def test_slo_latency_percentiles_merge_across_tenants():
    t, clock = mk_tracker(targets={"gold": 0.9})
    # one fast tenant, one slow tenant, plus untenanted traffic —
    # the class percentiles must see ALL of it
    for _ in range(10):
        t.record("gold", "fast-co", 0.01, "ok")
    for _ in range(10):
        t.record("gold", "slow-co", 4.0, "ok")
    t.record("gold", None, 0.01, "ok")
    lat = t.report()["classes"]["gold"]["latency_s"]
    assert lat["p99"] > 1.0, lat       # the slow tenant is visible
    assert lat["p50"] < 1.0, lat


# ---------------------------------------------------------------------------
# SLOTracker
# ---------------------------------------------------------------------------

def mk_tracker(**kw):
    clock = {"t": 1000.0}
    kw.setdefault("registry", MetricRegistry())
    kw.setdefault("windows", (10.0, 100.0))
    kw.setdefault("breach_threshold", 5.0)
    kw.setdefault("min_samples", 4)
    t = SLOTracker(clock=lambda: clock["t"], **kw)
    return t, clock


def test_burn_rate_math():
    t, clock = mk_tracker(targets={"gold": 0.9})   # 10% budget
    for i in range(8):
        t.record("gold", None, 0.01, "ok")
    for i in range(2):
        t.record("gold", None, 0.01, "deadline")
    # 2 errors / 10 requests = 20% error rate; budget 10% → burn 2.0
    assert t.burn_rates("gold") == {"short": pytest.approx(2.0),
                                    "long": pytest.approx(2.0)}
    assert t.breached() == []          # burn 2.0 < threshold 5.0


def test_short_window_forgets_old_errors():
    t, clock = mk_tracker(targets={"gold": 0.9})
    for _ in range(5):
        t.record("gold", None, 0.01, "error")
    assert t.burn_rates("gold")["short"] == pytest.approx(10.0)
    clock["t"] += 20.0                 # past the 10s short window
    for _ in range(5):
        t.record("gold", None, 0.01, "ok")
    rates = t.burn_rates("gold")
    assert rates["short"] == 0.0       # errors aged out
    assert rates["long"] == pytest.approx(5.0)   # still in the 100s


def test_breach_latches_only_on_both_windows_and_is_sticky():
    t, clock = mk_tracker(targets={"gold": 0.99})
    for _ in range(6):
        t.record("gold", None, 0.01, "deadline")
    assert t.breached() == ["gold"]
    assert t.health() == "degraded"
    g = t.registry.get("slo_breach_latched")
    assert g.labels("gold").value == 1
    # traffic recovers; the latch stays until acknowledged
    clock["t"] += 200.0
    for _ in range(10):
        t.record("gold", None, 0.01, "ok")
    assert t.burn_rates("gold") == {"short": 0.0, "long": 0.0}
    assert t.breached() == ["gold"]
    t.reset_breach()
    assert t.breached() == [] and t.health() == "healthy"
    assert g.labels("gold").value == 0


def test_min_samples_gates_the_latch():
    t, clock = mk_tracker(targets={"gold": 0.99}, min_samples=10)
    for _ in range(5):                  # burning hard, but thin data
        t.record("gold", None, 0.01, "error")
    assert t.burn_rates("gold")["short"] > 5.0
    assert t.breached() == []


def test_cancelled_burns_no_budget():
    t, clock = mk_tracker(targets={"gold": 0.5})
    for _ in range(6):
        t.record("gold", None, 0.01, "cancelled")
    assert t.burn_rates("gold") == {"short": 0.0, "long": 0.0}
    rep = t.report()
    assert rep["classes"]["gold"]["windows"]["short"]["requests"] == 0


def test_deadline_hit_ratio_counts_only_deadline_carriers():
    t, clock = mk_tracker()
    t.record("x", None, 0.01, "ok", had_deadline=True)
    t.record("x", None, 0.01, "ok", had_deadline=True)
    t.record("x", None, 0.01, "deadline", had_deadline=True)
    t.record("x", None, 0.01, "ok", had_deadline=False)   # neutral
    rep = t.report()["classes"]["x"]
    assert rep["deadline_hits"] == 2 and rep["deadline_misses"] == 1
    assert rep["deadline_hit_ratio"] == pytest.approx(2 / 3)
    assert t.registry.get("slo_deadline_hit_ratio") \
        .labels("x").value == pytest.approx(2 / 3)


def test_report_shape_and_latency_percentiles():
    t, clock = mk_tracker(targets={"gold": 0.95})
    for ms in (10, 20, 30):
        t.record("gold", None, ms / 1000.0, "ok")
    rep = t.report()
    gold = rep["classes"]["gold"]
    assert gold["target"] == 0.95
    assert gold["error_budget"] == pytest.approx(0.05)
    assert gold["windows"]["short"]["requests"] == 3
    assert gold["windows"]["short"]["window_s"] == 10.0
    assert "p99" in gold["latency_s"]
    assert rep["breached"] == []


def test_tenant_label_lands_on_latency_histogram():
    t, clock = mk_tracker()
    t.record("gold", "acme", 0.05, "ok")
    fam = t.registry.get("slo_request_seconds")
    assert fam.labels("gold", "acme").count == 1


# ---------------------------------------------------------------------------
# router integration: /fleetz, /sloz, /healthz latch, reset
# ---------------------------------------------------------------------------

class ObsStub:
    """Stub replica with a metrics surface."""

    def __init__(self, tokens=10):
        self.tokens = tokens
        self.n = 0
        self._mu = threading.Lock()

    def submit(self, prompt_ids, **kw):
        with self._mu:
            self.n += 1
        return {"output_ids": [1] * kw.get("max_new_tokens", 1),
                "prompt_ids": list(prompt_ids)}

    def health(self):
        return "healthy"

    def metrics_text(self):
        return replica_text(tokens=self.tokens, done=self.n)

    def cancel(self, request_id):
        return False

    def close(self):
        pass


def _get_json(url, timeout=30):
    with urlopen(url, timeout=timeout) as r:
        return r.status, json.loads(r.read())


@pytest.fixture()
def obs_router():
    stubs = {"r0": ObsStub(tokens=10), "r1": ObsStub(tokens=30)}
    router = Router(
        stubs, health_poll_interval=0.05, page_size=16,
        slo_classes={"gold": SLOClass("gold", deadline_s=30.0,
                                      target=0.9)},
        # a short window of minutes, not of 5 s: on a loaded host a stall of
        # seconds between a storm's last miss and the read of /sloz emptied
        # the window and the burn rate read 0 (the driver's tier-1 run of
        # PR 39's tree; PR 36 saw it too)
        slo_windows=(60.0, 600.0), slo_min_samples=4,
        slo_breach_threshold=5.0)
    srv = DebugServer(port=0).start()
    yield stubs, router, f"http://127.0.0.1:{srv.port}"
    router.close()
    srv.stop()


def _wait(fn, timeout=10.0, what="condition"):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        v = fn()
        if v:
            return v
        time.sleep(0.02)
    raise AssertionError(f"timed out waiting for {what}")


def test_fleetz_over_http_aggregates_and_labels(obs_router):
    stubs, router, base = obs_router
    for i in range(4):
        router.submit([i, i + 1, i + 2], max_new_tokens=2) \
            .result(timeout=30)

    def both_scraped():
        _code, fz = _get_json(base + "/fleetz")
        fleet = next(iter(fz["fleets"].values()))
        reps = fleet["replicas"]
        ok = all((reps[n].get("metrics") or {}).get("up")
                 for n in ("r0", "r1"))
        return fleet if ok else None

    fleet = _wait(both_scraped, what="/fleetz scraping both stubs")
    assert fleet["aggregates"]["replicas_scraped"] == 2
    assert fleet["aggregates"]["tokens_generated"] == 40.0
    assert fleet["replicas"]["r0"]["breaker"] == "closed"
    assert fleet["replicas"]["r0"]["health"] == "healthy"
    # the federated block rides the router process's own /metrics
    with urlopen(base + "/metrics", timeout=30) as r:
        text = r.read().decode()
    assert 'fleet_llm_tokens_generated{replica="r0"} 10.0' in text
    assert 'fleet_llm_tokens_generated{replica="r1"} 30.0' in text
    assert "fleet_replicas_scraped 2.0" in text
    # exposition still parses line-by-line after the append
    for line in text.splitlines():
        if not line or line.startswith("#"):
            continue
        float(line.rsplit(" ", 1)[1].replace("+Inf", "inf"))


def test_sloz_burn_rate_moves_and_latch_shows_on_healthz(obs_router):
    stubs, router, base = obs_router
    from paddle_tpu.reliability.retry import DeadlineExceeded
    code, sz = _get_json(base + "/sloz")
    assert code == 200
    # deadline-miss storm on the gold class (hopeless by construction
    # — a tiny-but-positive deadline races the dispatch thread on a
    # fast host and the request can legitimately SUCCEED)
    futs = [router.submit([1, 2, 3], max_new_tokens=2, slo="gold",
                          deadline=-1.0) for _ in range(6)]
    for f in futs:
        with pytest.raises(DeadlineExceeded):
            f.result(timeout=30)
    _code, sz = _get_json(base + "/sloz")
    rep = next(iter(sz["slo"].values()))
    gold = rep["classes"]["gold"]
    assert gold["windows"]["short"]["burn_rate"] > 5.0
    assert gold["windows"]["long"]["burn_rate"] > 5.0
    assert rep["breached"] == ["gold"]
    # the latch is a degraded /healthz component
    _code, hz = _get_json(base + "/healthz")
    slo_components = {k: v for k, v in hz["components"].items()
                      if k.endswith("_slo")}
    assert list(slo_components.values()) == ["degraded"]
    assert hz["status"] == "degraded"
    # operator acknowledgment over HTTP clears it
    with urlopen(Request(base + "/reset_health", data=b"{}"),
                 timeout=30) as r:
        assert r.status == 200
    _code, sz = _get_json(base + "/sloz")
    assert next(iter(sz["slo"].values()))["breached"] == []


def test_sloz_and_fleetz_404_when_no_router(monkeypatch):
    from paddle_tpu.observability import server as dbg
    monkeypatch.setattr(dbg, "_fleet_providers", {})
    monkeypatch.setattr(dbg, "_slo_providers", {})
    srv = DebugServer(port=0).start()
    try:
        for path in ("/fleetz", "/sloz"):
            with pytest.raises(HTTPError) as ei:
                urlopen(f"http://127.0.0.1:{srv.port}{path}",
                        timeout=30)
            assert ei.value.code == 404
    finally:
        srv.stop()


def test_router_close_unregisters_fleet_surfaces(obs_router):
    stubs, router, base = obs_router
    router.close()
    with pytest.raises(HTTPError) as ei:
        urlopen(base + "/fleetz", timeout=30)
    assert ei.value.code == 404


# ---------------------------------------------------------------------------
# /tracez query filters + ts_wall
# ---------------------------------------------------------------------------

@pytest.fixture()
def traced_server():
    tracing.clear()
    tracing.enable()
    srv = DebugServer(port=0).start()
    yield f"http://127.0.0.1:{srv.port}"
    srv.stop()
    tracing.disable()
    tracing.clear()


def test_tracez_trace_id_and_limit_filters(traced_server):
    base = traced_server
    roots = []
    for i in range(3):
        root = tracing.start_span(f"req{i}", parent=None)
        tracing.start_span("child", parent=root).end()
        roots.append(root)
    roots[0].end()
    roots[1].end()          # roots[2] stays live
    target = roots[0].trace_id
    _code, tz = _get_json(base + f"/tracez?trace_id={target}")
    assert tz["finished_matched"] == 2
    assert {s["trace_id"] for s in tz["finished"]} == {target}
    assert {s["name"] for s in tz["finished"]} == {"req0", "child"}
    assert tz["live"] == []
    assert tz["finished_total"] == 5    # the unfiltered ring size
    # live spans filter too
    live_tid = roots[2].trace_id
    _code, tz = _get_json(base + f"/tracez?trace_id={live_tid}")
    assert [s["name"] for s in tz["live"]] == ["req2"]
    # limit applies after the filter; 0 = uncapped
    _code, tz = _get_json(base + f"/tracez?trace_id={target}&limit=1")
    assert len(tz["finished"]) == 1 and tz["finished_matched"] == 2
    _code, tz = _get_json(base + "/tracez?limit=0")
    assert len(tz["finished"]) == 5
    # every span carries ts_wall for cross-process alignment
    assert all(isinstance(s["ts_wall"], float)
               for s in tz["finished"])
    roots[2].end()


# ---------------------------------------------------------------------------
# trace_merge
# ---------------------------------------------------------------------------

def test_trace_merge_aligns_processes_on_wall_time(tmp_path):
    from tools.trace_merge import load_source, merge_chrome_trace
    tid = "a" * 32
    router_spans = [{
        "name": "router.dispatch", "trace_id": tid, "span_id": "r1",
        "parent_id": None, "ts": 5.0, "dur": 0.1, "tid": 1,
        "tname": "disp", "status": "ok", "attrs": {}, "events": [],
        "ts_wall": 100.0, "live": False,
        "links": [{"trace_id": tid, "span_id": "r0"}],
    }]
    # the replica's perf clock is wildly different; ts_wall aligns
    replica_spans = [{
        "name": "llm.request", "trace_id": tid, "span_id": "s1",
        "parent_id": "r1", "ts": 9000.0, "dur": 0.05, "tid": 7,
        "tname": "loop", "status": "ok", "attrs": {}, "ts_wall": 100.02,
        "events": [{"ts": 9000.01, "name": "chunk"}], "live": False,
    }, {
        "name": "other.trace", "trace_id": "b" * 32, "span_id": "s2",
        "parent_id": None, "ts": 9000.0, "dur": 0.01, "tid": 7,
        "tname": "loop", "status": "ok", "attrs": {}, "ts_wall": 100.5,
        "events": [], "live": False,
    }]
    # a flight-dump source as the third process
    flight = tmp_path / "flight_1_exception.jsonl"
    flight.write_text(
        json.dumps({"kind": "header", "reason": "exception"}) + "\n"
        + json.dumps({"kind": "span", "live": True,
                      "name": "llm.decode", "trace_id": tid,
                      "span_id": "s3", "parent_id": "s1", "ts": 1.0,
                      "dur": None, "tid": 2, "status": "ok",
                      "attrs": {}, "events": [],
                      "ts_wall": 100.04}) + "\n")
    out = tmp_path / "merged.json"
    summary = merge_chrome_trace(
        {"router": router_spans, "r0": replica_spans,
         "r0-flight": load_source(str(flight))},
        str(out), trace_id=tid)
    assert summary["spans"] == 3       # other.trace filtered out
    assert summary["trace_ids"] == 1
    assert summary["links"] == 1
    chrome = json.loads(out.read_text())
    evs = chrome["traceEvents"]
    pnames = {e["args"]["name"] for e in evs
              if e["name"] == "process_name"}
    assert pnames == {"router", "r0", "r0-flight"}
    spans = {e["name"]: e for e in evs if e["ph"] == "X"}
    assert set(spans) == {"router.dispatch", "llm.request",
                          "llm.decode"}
    # wall alignment: t0 = earliest ts_wall (100.0) → dispatch at 0,
    # llm.request at 20ms, llm.decode at 40ms — perf clocks ignored
    assert spans["router.dispatch"]["ts"] == pytest.approx(0.0)
    assert spans["llm.request"]["ts"] == pytest.approx(20_000, rel=1e-3)
    assert spans["llm.decode"]["ts"] == pytest.approx(40_000, rel=1e-3)
    assert spans["llm.request"]["pid"] != spans["router.dispatch"]["pid"]
    assert spans["llm.decode"]["args"]["live"] is True
    assert spans["router.dispatch"]["args"]["links"] == [
        {"trace_id": tid, "span_id": "r0"}]
    # the span event converted through its span's wall offset
    inst = [e for e in evs if e["ph"] == "i"]
    assert inst and inst[0]["name"] == "llm.request:chunk"
    assert inst[0]["ts"] == pytest.approx(30_000, rel=1e-3)


def test_trace_merge_loads_tracez_url(traced_server):
    from tools.trace_merge import load_source, merge_chrome_trace
    base = traced_server
    root = tracing.start_span("req", parent=None)
    tracing.start_span("child", parent=root).end()
    root.end()
    spans = load_source(base + "/tracez")
    assert {s["name"] for s in spans} == {"req", "child"}
    assert all("ts_wall" in s for s in spans)
    out = "/tmp/pt_trace_merge_url_test.json"
    summary = merge_chrome_trace({"p": spans}, out,
                                 trace_id=root.trace_id)
    assert summary["spans"] == 2
