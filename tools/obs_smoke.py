"""Observability smoke gate (tools/ci.sh step): run a tiny instrumented
train loop under the profiler WITH TRACING ON, dump every exporter, and
assert the artifacts parse — Prometheus text exposition, the
chrome://tracing JSON (≥1 complete "X" event per recorded host
annotation, plus span events with parent links and row-label metadata),
and the JSONL reporter stream. Then exercise the live surfaces: start
the debug server on an ephemeral port and scrape /metrics, /healthz,
/statusz, /tracez and /perfz — the perf gate asserts nonzero live MFU
after the fit run, resolved XLA program costs for the fused train
loop AND a decode-slab LLMEngine pass, breakdown phases that
reproduce the dispatch/drain histogram totals, and the per-tenant
served-FLOPs counter; finally force-crash a subprocess with the
flight recorder installed and assert the JSONL dump was written. Exits
non-zero on any missing signal so a refactor that silently unhooks an
instrument fails CI, not a 3am bench round.

FLEET MODE (``--fleet``): spawn K=2 replica subprocesses behind a
Router and assert the fleet-wide observability holds — ``GET /fleetz``
aggregates both replicas with per-replica data, the router's
``/metrics`` re-exports replica-labeled ``fleet_llm_*`` series, a
request's spans form ONE cross-process trace (router.request →
router.dispatch here, llm.request in the replica, fetched back via
``/tracez?trace_id=``), ``tools/trace_merge.py`` joins the tables onto
one timeline, and — the PR-4 regression criterion — DISABLED tracing
still costs one flag check (start_span returns the shared noop, time-
bounded).

Run: python tools/obs_smoke.py [outdir] [--fleet]
"""

import json
import os
import subprocess
import sys
import urllib.error
import urllib.request

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402


def main(outdir: str = "/tmp/pt_obs_smoke") -> int:
    import paddle_tpu as pt
    from paddle_tpu import nn, observability
    from paddle_tpu.io import TensorDataset
    from paddle_tpu.observability import server as debug_server
    from paddle_tpu.observability import tracing
    from paddle_tpu.profiler import Profiler, export_chrome_tracing

    os.makedirs(outdir, exist_ok=True)
    pt.seed(0)
    net = nn.Sequential(nn.Linear(8, 16), nn.ReLU(), nn.Linear(16, 2))
    model = pt.Model(net)
    model.prepare(optimizer=pt.optimizer.SGD(learning_rate=0.1,
                                             parameters=net),
                  loss=nn.CrossEntropyLoss())
    x = np.random.RandomState(0).randn(64, 8).astype(np.float32)
    y = np.random.RandomState(1).randint(0, 2, (64, 1))

    jsonl_path = os.path.join(outdir, "metrics.jsonl")
    prof = Profiler(log_dir=os.path.join(outdir, "xprof"))
    tracing.enable()
    with observability.JSONLReporter(jsonl_path, interval=0.2):
        prof.start()
        model.fit(TensorDataset([x, y]), batch_size=16, epochs=2,
                  verbose=0, steps_per_loop=2)
        prof.stop()
    observability.sample_device_memory()

    # -- chrome trace: loads, covers every annotation AND the spans -----
    trace_path = export_chrome_tracing(prof,
                                       os.path.join(outdir, "trace.json"))
    with open(trace_path) as f:
        trace = json.load(f)
    events = trace["traceEvents"]
    assert events, "empty chrome trace"
    xs = [ev for ev in events if ev["ph"] == "X"]
    assert all(ev["dur"] >= 0 for ev in xs)
    names = {ev["name"] for ev in xs}
    for phase in ("fit.next_batch", "fit.dispatch", "fit.callbacks"):
        assert phase in names, (phase, names)
    for bucket in ("Dataloader", "TrainStep", "Callbacks"):
        assert bucket in prof.summary(), bucket
    # spans merged onto the same timeline with parent links + metadata
    span_evs = [ev for ev in xs if ev.get("cat") == "span"]
    span_names = {ev["name"] for ev in span_evs}
    for want in ("train.epoch", "train.dispatch"):
        assert want in span_names, (want, span_names)
    epoch_ids = {ev["args"]["span_id"] for ev in span_evs
                 if ev["name"] == "train.epoch"}
    step_parents = {ev["args"]["parent_id"] for ev in span_evs
                    if ev["name"] == "train.dispatch"}
    assert step_parents <= epoch_ids, \
        "train.dispatch not parented to epoch"
    meta = {ev["name"] for ev in events if ev["ph"] == "M"}
    assert {"process_name", "thread_name"} <= meta, meta

    # -- prometheus text: parses line-by-line, has the train signals ----
    prom_path = observability.write_prometheus(
        os.path.join(outdir, "metrics.prom"))
    with open(prom_path) as f:
        text = f.read()
    for line in text.splitlines():
        if not line or line.startswith("#"):
            continue
        name_part, value = line.rsplit(" ", 1)
        float(value)            # every sample value is a number
        assert name_part[0].isalpha() or name_part[0] == "_", line
    assert "train_step_seconds_count" in text
    assert "train_loop_slabs" in text     # fused-loop feed instrumented
    assert "train_loop_dispatch_seconds" in text

    # -- jsonl stream: every line self-contained JSON with metrics ------
    with open(jsonl_path) as f:
        lines = [json.loads(ln) for ln in f if ln.strip()]
    assert lines, "JSONL reporter wrote nothing"
    assert any(rec["metrics"].get("train_step_seconds_count", 0) > 0
               for rec in lines), "no step metrics reached the JSONL dump"

    # -- debug server: live /metrics + /statusz + /tracez round-trip ----
    srv = debug_server.DebugServer(port=0).start()
    try:
        base = f"http://127.0.0.1:{srv.port}"
        with urllib.request.urlopen(base + "/healthz", timeout=30) as r:
            assert json.loads(r.read())["status"] == "ok"
        with urllib.request.urlopen(base + "/metrics", timeout=30) as r:
            scraped = r.read().decode()
            assert "version=0.0.4" in r.headers["Content-Type"]
        for fam in ("train_step_seconds", "train_compile_count",
                    "train_loop_slabs", "train_loop_dispatch_seconds"):
            assert fam in scraped, f"{fam} missing from /metrics scrape"
        for line in scraped.splitlines():     # scrape parses too
            if not line or line.startswith("#"):
                continue
            float(line.rsplit(" ", 1)[1].replace("+Inf", "inf"))
        with urllib.request.urlopen(base + "/statusz", timeout=30) as r:
            st = json.loads(r.read())
        assert any(k.startswith("train_model_") for k in st["providers"])
        # CPU backends export no memory_stats: /statusz must show the
        # documented host-RSS fallback, never a bare misleading {}
        devmem = st["device_memory"]
        assert devmem, "/statusz device_memory is an empty dict"
        if not any(isinstance(v, dict) for v in devmem.values()):
            assert devmem.get("host_rss_bytes"), devmem
        assert st.get("memory", {}).get("enabled") is True, \
            st.get("memory")
        assert st.get("goodput", {}).get("enabled") is True, \
            st.get("goodput")
        with urllib.request.urlopen(base + "/tracez?limit=8",
                                    timeout=30) as r:
            tz = json.loads(r.read())
        assert tz["finished_total"] > 0

        # -- /perfz: live MFU + step-time breakdown for the fit run ----
        # (the continuous-perf acceptance: nonzero MFU after a few
        # steps, and the breakdown phases reproduce the step-time
        # totals the histograms measured — same clocks, no drift)
        assert st.get("perf", {}).get("enabled") is True, st.get("perf")
        with urllib.request.urlopen(base + "/perfz", timeout=60) as r:
            pz = json.loads(r.read())
        assert pz["enabled"], pz
        assert pz["mfu"] > 0, f"zero MFU after a fit run: {pz}"
        assert pz["peaks"]["flops"] > 0
        train_progs = [p for p in pz["programs"]
                       if p["component"] == "train"]
        assert train_progs and any(
            p["cost_resolved"] and p["flops"] and p["dispatches"] > 0
            for p in train_progs), train_progs
        ph = pz["breakdown"]["train"]["phases"]
        assert ph.get("dispatch", 0) > 0, ph
        reg = observability.default_registry()
        loop_hist = reg.get("train_loop_dispatch_seconds")
        dispatched = loop_hist.sum if loop_hist is not None else 0.0
        phase_sum = ph.get("dispatch", 0.0) + ph.get("compile", 0.0)
        # the fit ran entirely through the fused loop: compile+dispatch
        # phases are the SAME dt values the dispatch histogram observed
        assert dispatched > 0 and \
            abs(phase_sum - dispatched) / dispatched < 0.05, \
            (phase_sum, dispatched, ph)
        drain_hist = reg.get("train_loop_drain_seconds")
        if drain_hist is not None and drain_hist.sum > 0:
            assert abs(ph.get("drain", 0.0) - drain_hist.sum) \
                / drain_hist.sum < 0.05, (ph, drain_hist.sum)
        with urllib.request.urlopen(base + "/metrics", timeout=30) as r:
            rescraped = r.read().decode()
        assert "perf_mfu" in rescraped and \
            "perf_flops_per_second" in rescraped, \
            "perf gauges missing from /metrics"

        # -- /memz after the fit: train trees attributed ---------------
        # (the engine half of the /memz acceptance — kv_pool split,
        # headroom, pool-exactness — runs in _engine_perf_section
        # while the engine is LIVE)
        with urllib.request.urlopen(base + "/memz", timeout=30) as r:
            mz = json.loads(r.read())
        assert mz["enabled"], mz
        assert mz["attributed_device_bytes"] > 0, \
            f"nothing attributed after a fit run: {mz}"
        owners = {r["owner"] for r in mz["owners"]}
        assert "train_params" in owners, owners
        # the residual line must EXIST either way: a real number on
        # backends with memory_stats, an explicit null + note on CPU
        assert "unattributed_bytes" in mz, sorted(mz)
        if mz["device"] is not None:
            assert mz["attributed_device_bytes"] <= \
                mz["device"]["bytes_in_use"], mz
            assert abs(mz["attributed_device_bytes"]
                       + mz["unattributed_bytes"]
                       - mz["device"]["bytes_in_use"]) < 1, mz
        else:
            assert mz["unattributed_bytes"] is None
            assert mz["unattributed_note"], mz
        assert mz["watermarks"], "no phase watermark recorded"

        # -- /perfz + /memz for a decode-slab LLMEngine run ------------
        _engine_perf_section(base)

        # -- /goodputz: the time ledger after fit + engine pass --------
        _goodput_section(base)
    finally:
        srv.stop()
    tracing.disable()

    # -- flight recorder: forced crash leaves a JSONL dump --------------
    crash_dir = os.path.join(outdir, "flight")
    crash_code = f"""
import jax; jax.config.update("jax_platforms", "cpu")
from paddle_tpu.observability import tracing, flight
tracing.enable()
flight.install_flight_recorder({crash_dir!r})
tracing.start_span("doomed.work", attrs={{"step": 7}})
raise RuntimeError("forced crash for the obs smoke gate")
"""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "-c", crash_code], env=env,
                       capture_output=True, text=True, timeout=300,
                       cwd=os.path.dirname(os.path.dirname(
                           os.path.abspath(__file__))))
    assert p.returncode != 0, "forced crash exited 0"
    assert "forced crash" in p.stderr, p.stderr[-500:]
    dumps = [f for f in os.listdir(crash_dir) if f.endswith(".jsonl")]
    assert dumps, "flight recorder wrote no dump on unhandled exception"
    rows = [json.loads(ln)
            for ln in open(os.path.join(crash_dir, dumps[0]))]
    assert rows[0]["kind"] == "header" and rows[0]["reason"] == "exception"
    assert any(r.get("kind") == "span" and r.get("live") and
               r["name"] == "doomed.work" for r in rows), \
        "in-flight span missing from the crash dump"

    print(f"observability smoke OK: {len(events)} trace events "
          f"({len(span_evs)} spans), {len(text.splitlines())} prom "
          f"lines, {len(lines)} jsonl rows, debug server scraped, "
          f"/perfz mfu={pz['mfu']:.4g} (train+llm programs costed), "
          f"crash dump {dumps[0]} -> {outdir}")
    return 0


def _engine_perf_section(base: str) -> None:
    """Decode-slab half of the /perfz acceptance: a tiny LLMEngine at
    decode_ticks_per_dispatch=4 serves a couple of requests, then
    /perfz must show the fused-slab program with resolved cost, a
    nonzero llm MFU contribution, the decode phase in the breakdown,
    and the per-tenant served-FLOPs counter."""
    import paddle_tpu as pt
    from paddle_tpu import observability
    from paddle_tpu.inference.llm import LLMEngine
    from paddle_tpu.models.gpt import GPTForCausalLM, gpt_config

    pt.seed(0)
    cfg = gpt_config("gpt2-small", num_layers=2, hidden_size=64,
                     num_heads=4, vocab_size=97,
                     max_position_embeddings=128,
                     hidden_dropout=0.0, attention_dropout=0.0)
    net = GPTForCausalLM(cfg)
    rng = np.random.RandomState(0)
    prompts = [rng.randint(0, 97, 8).tolist() for _ in range(3)]
    with LLMEngine(net, max_seqs=4, page_size=8, num_pages=32,
                   max_len=64, prefill_chunk=8,
                   decode_ticks_per_dispatch=4) as eng:
        outs = [eng.submit(p, max_new_tokens=24,
                           tenant="smoke").result(timeout=240)
                for p in prompts]
        # /perfz while the engine is LIVE (close() drops its program
        # entries from the registry; the windowed rates persist)
        with urllib.request.urlopen(base + "/perfz", timeout=60) as r:
            pz = json.loads(r.read())
        # /memz while the engine is LIVE: the kv_pool split must tile
        # the pool exactly (free + private + prefix_shared + scratch
        # == num_pages x page_bytes) and sit under the device total
        # where the backend reports one
        with urllib.request.urlopen(base + "/memz", timeout=60) as r:
            mz = json.loads(r.read())
        kv = {r["kind"]: r["bytes"] for r in mz["owners"]
              if r["owner"] == "kv_pool"}
        assert set(kv) == {"free", "private", "prefix_shared",
                           "scratch"}, kv
        page_bytes = eng._page_bytes
        assert sum(kv.values()) == eng.num_pages * page_bytes, \
            (kv, eng.num_pages, page_bytes)
        assert mz["headroom"] is not None and \
            mz["headroom"]["kv_pages_addable"] > 0, mz["headroom"]
        if mz["device"] is not None:
            assert mz["attributed_device_bytes"] <= \
                mz["device"]["bytes_in_use"], mz
        # the gauges ride the same read: the federation scrape path
        with urllib.request.urlopen(base + "/metrics", timeout=30) as r:
            scraped = r.read().decode()
        assert "mem_headroom_pages" in scraped and \
            "mem_bytes{" in scraped and \
            "mem_watermark_bytes" in scraped, \
            "mem gauges missing from /metrics"
    assert all(o["output_ids"] for o in outs)
    assert all(o.get("served_flops", 0) > 0 for o in outs), outs
    slabs = [p for p in pz["programs"]
             if p["component"] == "llm" and p["kind"] == "decode_loop"]
    assert slabs and any(p["cost_resolved"] and p["dispatches"] > 0
                         for p in slabs), pz["programs"]
    llm_ph = pz["breakdown"].get("llm", {}).get("phases", {})
    assert llm_ph.get("decode", 0) > 0, pz["breakdown"]
    snap = observability.default_registry().snapshot()
    assert snap.get('llm_served_flops_total{tenant="smoke"}', 0) > 0, \
        {k: v for k, v in snap.items() if "served" in k}


def _goodput_section(base: str) -> None:
    """Tentpole acceptance for the time ledger: after the fit run AND
    the decode-slab engine pass, ``/goodputz`` must show nonzero
    productive seconds, a reconciliation line whose buckets +
    unattributed sum exactly to elapsed, and device-time buckets that
    reproduce the totals the perf instruments measured — the ledger
    rides the SAME dt values (train: the fused-loop dispatch
    histogram; llm: the /perfz breakdown phases), so on this serial
    workload the interval union equals the sums."""
    from paddle_tpu import observability

    code, gz = _get_json(base + "/goodputz")
    assert code == 200
    assert gz["enabled"] and gz["armed"], gz
    assert gz["buckets"]["productive"] > 0, \
        f"zero productive time after a fit + engine run: {gz['buckets']}"
    rec = gz["reconciliation"]
    assert abs(rec["attributed_s"] + rec["unattributed_s"]
               - rec["elapsed_s"]) < 1e-6, rec
    assert abs(rec["residual_s"]) < 1e-6, rec
    # device-time buckets vs the perf instruments' totals
    reg = observability.default_registry()
    loop_hist = reg.get("train_loop_dispatch_seconds")
    dispatched = loop_hist.sum if loop_hist is not None else 0.0
    code, pz = _get_json(base + "/perfz")
    llm_ph = pz["breakdown"].get("llm", {}).get("phases", {})
    expect = dispatched + sum(llm_ph.values())
    got = gz["buckets"]["productive"] + gz["buckets"]["compile"]
    assert expect > 0 and abs(got - expect) / expect < 0.05, \
        (got, expect, gz["buckets"], llm_ph)
    # the gauges ride the /metrics prescrape (the federation surface)
    with urllib.request.urlopen(base + "/metrics", timeout=30) as r:
        scraped = r.read().decode()
    assert "goodput_fraction" in scraped, \
        "goodput_fraction gauge missing from /metrics"
    assert 'badput_seconds_total{cause=' in scraped, \
        "badput_seconds_total counters missing from /metrics"


def _get_json(url: str, timeout: float = 30.0):
    with urllib.request.urlopen(url, timeout=timeout) as r:
        return r.status, json.loads(r.read())


def fleet_main(outdir: str = "/tmp/pt_obs_fleet_smoke") -> int:
    import time

    from paddle_tpu.observability import server as debug_server
    from paddle_tpu.observability import tracing
    from paddle_tpu.serving import HTTPReplica, Router, spawn_replica
    from tools.trace_merge import load_source, merge_chrome_trace

    os.makedirs(outdir, exist_ok=True)
    obs_dir = os.path.join(outdir, "obs")
    model = {"platform": "cpu",   # a CPU-only gate by design
             "vocab": 97, "layers": 2, "hidden": 64, "heads": 4,
             "max_pos": 96, "model_seed": 0, "tracing": True,
             "obs_dir": obs_dir,
             "engine": {"seed": 0, "max_pending": 64}}
    names = ("r0", "r1")
    tracing.enable()
    # setup happens INSIDE the try: a spawn/warm-up failure must
    # still kill whatever replica subprocesses already exist
    procs, infos = {}, {}
    router, srv = None, None
    try:
        # staggered spawn: r0 warms the shared compile cache for r1
        procs["r0"], infos["r0"] = spawn_replica(
            dict(model, name="r0"), timeout=240)
        HTTPReplica(infos["r0"]["generate"],
                    infos["r0"]["healthz"]).submit([1, 2, 3],
                                                   max_new_tokens=2)
        procs["r1"], infos["r1"] = spawn_replica(
            dict(model, name="r1"), timeout=240)
        router = Router(
            {n: HTTPReplica(infos[n]["generate"], infos[n]["healthz"],
                            metrics_url=infos[n]["metrics"])
             for n in names},
            health_poll_interval=0.2, page_size=4, affinity_pages=2)
        srv = debug_server.DebugServer(port=0).start()
        base = f"http://127.0.0.1:{srv.port}"
        # hole-not-zero over HTTP: before any stream verification this
        # process has no drift table — /driftz must 404, not serve
        # an all-zero (falsely clean) body
        try:
            _get_json(base + "/driftz")
            raise AssertionError("/driftz answered before any stream "
                                 "verification armed the auditor")
        except urllib.error.HTTPError as e:
            assert e.code == 404, f"/driftz pre-arm status {e.code}"
        # shadow every request below so the drift surfaces have data
        # (the replicas themselves never record a verdict here — their
        # /driftz stays a 404 hole, pinned further down)
        from paddle_tpu.core import flags as _flags
        _flags.set_flags({"audit_shadow_rate": 1.0})
        from paddle_tpu.serving.router import (affinity_key,
                                               rendezvous_pick)
        import numpy as np

        def prompt_for(target, length=12, seed=0):
            # rejection-sample a prompt whose affinity preference is
            # `target` — BOTH replicas must serve traffic for the
            # per-replica federation assertions to mean anything
            rng = np.random.RandomState(seed)
            while True:
                p = rng.randint(0, 97, length).tolist()
                key = affinity_key(p, router.page_size,
                                   router.affinity_pages)
                if rendezvous_pick(key, names) == target:
                    return p

        outs = [router.submit(prompt_for(n, seed=i), max_new_tokens=4)
                .result(timeout=240)
                for i, n in enumerate(names * 2)]
        assert all(o["output_ids"] for o in outs)
        assert {o["replica"] for o in outs} == set(names), outs
        deadline = time.monotonic() + 20
        while time.monotonic() < deadline:
            code, fz = _get_json(base + "/fleetz")
            fleet = next(iter(fz["fleets"].values()))
            reps = fleet["replicas"]
            # wait for a scrape taken AFTER the traffic: EACH
            # replica's own completed work must be visible (an "up"
            # verdict can come from a pre-traffic scrape cycle)
            if all(n in reps and (reps[n].get("metrics") or {})
                   .get("requests_completed") for n in names):
                break
            time.sleep(0.2)
        else:
            raise AssertionError(f"/fleetz never aggregated both "
                                 f"replicas' traffic: {fz}")
        # -- /fleetz: per-replica data + computed aggregates ------------
        agg = fleet["aggregates"]
        assert agg["replicas_scraped"] == 2, agg
        assert any((reps[n]["metrics"] or {}).get("requests_completed")
                   for n in names), reps
        # -- /metrics: replica-labeled federated series -----------------
        with urllib.request.urlopen(base + "/metrics", timeout=30) as r:
            scraped = r.read().decode()
        for n in names:
            assert f'fleet_llm_requests_completed{{replica="{n}"}}' \
                in scraped, f"federated series for {n} missing"
        assert "fleet_prefix_cache_hit_rate" in scraped
        assert "router_dispatches_total" in scraped
        # perf federation: replica perf_* gauges ride the same scrape
        # and aggregate into fleet_mfu (holes for down replicas —
        # pinned unit-side in tests/test_perf_observability.py)
        assert 'fleet_perf_mfu{replica=' in scraped, \
            "replica perf gauges not federated"
        assert "fleet_mfu " in scraped or "fleet_mfu{" in scraped, \
            "fleet_mfu aggregate missing"
        # memory federation: each replica's pool headroom rides the
        # same scrape and sums into fleet_mem_headroom_pages (holes
        # for down replicas — pinned unit-side in
        # tests/test_memory_observability.py)
        assert 'fleet_mem_headroom_pages{replica=' in scraped, \
            "replica mem_headroom_pages not federated"
        assert "fleet_headroom_pages " in scraped, \
            "fleet_headroom_pages aggregate missing"
        # goodput federation: both replicas served traffic, so both
        # time ledgers armed and export goodput_fraction — the fleet
        # aggregate must be a mean over BOTH (auditable denominator),
        # with the per-replica badput causes federated alongside
        assert "fleet_goodput_fraction " in scraped, \
            "fleet_goodput_fraction aggregate missing"
        assert "fleet_goodput_replicas 2" in scraped, \
            "fleet_goodput_fraction mean must cover both replicas"
        assert 'fleet_badput_seconds_total{replica=' in scraped, \
            "replica badput causes not federated"
        for n in names:
            assert (reps[n].get("metrics") or {}).get(
                "goodput_fraction") is not None, \
                f"/fleetz missing {n}'s goodput_fraction: {reps[n]}"
        # warming-replica-is-a-hole: a replica that is UP but has not
        # armed its time ledger (no goodput_fraction series yet) must
        # be ABSENT from the fleet mean, never a zero dragging it down
        from paddle_tpu.observability.metrics import MetricRegistry
        from paddle_tpu.serving.fleet import FleetScraper
        with urllib.request.urlopen(infos["r0"]["metrics"],
                                    timeout=30) as r:
            r0_text = r.read().decode()
        assert "goodput_fraction" in r0_text, \
            "armed replica exports no goodput_fraction"
        fs = FleetScraper(registry=MetricRegistry())
        fs.record("armed", r0_text)
        fs.record("warming", "llm_requests_completed 0\n")
        hole_agg = fs.aggregates()
        assert hole_agg["goodput_replicas"] == 1, hole_agg
        armed_frac = hole_agg["goodput_fraction"]
        assert armed_frac is not None and armed_frac > 0, hole_agg
        # -- stream-integrity drift surfaces ----------------------------
        # every request above was shadow re-executed (rate 1.0): the
        # router-side drift table armed, /driftz serves it, and the
        # fleet must prove itself CLEAN (zero divergences)
        deadline = time.monotonic() + 90
        dz = None
        while time.monotonic() < deadline:
            try:
                _code, dz = _get_json(base + "/driftz")
                if dz["drift"]["audit"]["totals"]["verified"] \
                        >= len(outs):
                    break
            except urllib.error.HTTPError:
                pass
            time.sleep(0.2)
        else:
            raise AssertionError(
                f"/driftz never accumulated {len(outs)} shadow "
                f"verdicts: {dz}")
        assert dz["drift"]["audit"]["enabled"] is True, dz
        assert dz["drift"]["audit"]["totals"]["diverged"] == 0, dz
        # the drift counters mint at first record and export locally…
        with urllib.request.urlopen(base + "/metrics", timeout=30) as r:
            scraped = r.read().decode()
        assert "drift_verified_total" in scraped, \
            "drift_verified_total missing after shadow verdicts"
        # …but NEITHER replica ever recorded a verdict: their /driftz
        # is a 404 and the fleet_drift_* aggregate reads them as holes
        # (denominator 0), never as zero-divergence evidence
        for n in names:
            try:
                _get_json(infos[n]["driftz"])
                raise AssertionError(
                    f"replica {n} served /driftz without recording")
            except urllib.error.HTTPError as e:
                assert e.code == 404, f"{n} /driftz status {e.code}"
        assert "fleet_drift_replicas 0" in scraped, \
            "never-armed replicas must be a hole in fleet_drift_*"
        assert 'fleet_drift_verified_total{replica=' not in scraped, \
            "replica exported drift series it never recorded"
        # an ARMED replica's counters do federate — and a replica
        # without them stays out of both sums and the denominator
        fs2 = FleetScraper(registry=MetricRegistry())
        fs2.record("armed", "drift_verified_total 5\n"
                   'drift_divergence_total{kind="shadow"} 1\n')
        fs2.record("hole", "llm_requests_completed 0\n")
        agg2 = fs2.aggregates()
        assert agg2["drift_replicas"] == 1, agg2
        assert agg2["drift_verified"] == 5, agg2
        assert agg2["drift_divergences"] == 1, agg2
        # -- brownout federation is hole-not-zero ------------------------
        # no replica in this smoke runs an overload controller, so the
        # fleet MAX has an explicitly empty denominator — a fleet that
        # exports level 0 here would be claiming "all clear" on the
        # strength of replicas that never took the measurement
        assert "fleet_brownout_replicas 0" in scraped, \
            "controller-less replicas must be a hole in " \
            "fleet_brownout_level, never level-0 evidence"
        fs3 = FleetScraper(registry=MetricRegistry())
        fs3.record("browned", "brownout_level 2\n")
        fs3.record("hole", "llm_requests_completed 0\n")
        agg3 = fs3.aggregates()
        assert agg3["brownout_replicas"] == 1, agg3
        assert agg3["brownout_level"] == 2, agg3   # MAX over UP, not mean
        _flags.set_flags({"audit_shadow_rate": 0.0})
        # -- ONE cross-process trace ------------------------------------
        out = outs[0]
        tid = out["trace_id"]
        assert tid and len(tid) == 32, out
        local = [s for s in tracing.finished_spans()
                 if s["trace_id"] == tid]
        lnames = {s["name"] for s in local}
        assert {"router.request", "router.dispatch"} <= lnames, lnames
        dispatch = [s for s in local if s["name"] == "router.dispatch"]
        replica = out["replica"]
        code, tz = _get_json(
            infos[replica]["tracez"] + f"?trace_id={tid}")
        rspans = {s["name"]: s for s in tz["finished"]}
        assert "llm.request" in rspans, (
            f"replica {replica} has no llm.request for trace {tid}: "
            f"{sorted(rspans)}")
        req_span = rspans["llm.request"]
        assert req_span["trace_id"] == tid
        assert req_span["parent_id"] in {d["span_id"] for d in dispatch}
        assert req_span["attrs"].get("remote_parent") is True
        # the replica-side phases share the trace too
        assert any(n.startswith("llm.") and n != "llm.request"
                   for n in rspans), sorted(rspans)
        # -- merged timeline via trace_merge ----------------------------
        sources = {"router": load_source(base + "/tracez"),
                   **{n: load_source(infos[n]["tracez"])
                      for n in names}}
        merged = merge_chrome_trace(
            sources, os.path.join(outdir, "merged.json"), trace_id=tid)
        assert merged["spans"] >= 3, merged
        assert merged["trace_ids"] == 1, merged
        with open(merged["path"]) as f:
            chrome = json.load(f)
        pnames = {e["args"]["name"] for e in chrome["traceEvents"]
                  if e["name"] == "process_name"}
        assert {"router", "r0", "r1"} <= pnames, pnames
        # -- /sloz answers (burn-rate movement is chaos-soak-asserted) --
        code, sz = _get_json(base + "/sloz")
        assert code == 200
        classes = next(iter(sz["slo"].values()))["classes"]
        assert "default" in classes, classes
        assert classes["default"]["windows"]["short"]["requests"] > 0
        # -- flight/JSONL artifacts landed under the obs_dir knob -------
        for n in names:
            jl = os.path.join(obs_dir, n, "metrics.jsonl")
            assert os.path.exists(jl), f"{n} JSONL reporter wrote nothing"
        # -- PR-4 regression criterion: disabled tracing = one flag
        # check. Structural half: the shared noop comes back (no Span,
        # no table write). Timing half: a generous per-call bound that
        # still catches accidentally creating real spans.
        tracing.disable()
        sp = tracing.start_span("ghost")
        assert sp is tracing.NOOP_SPAN
        n_calls = 200_000
        t0 = time.perf_counter()
        for _ in range(n_calls):
            tracing.start_span("ghost")
        per_call = (time.perf_counter() - t0) / n_calls
        assert per_call < 5e-6, (
            f"disabled start_span costs {per_call * 1e6:.2f}us/call — "
            f"more than a flag check")
    finally:
        tracing.disable()
        if router is not None:
            router.close()
        if srv is not None:
            srv.stop()
        for p in procs.values():
            if p.poll() is None:
                p.kill()
                p.wait(timeout=30)
    print(f"fleet observability smoke OK: 2 replicas federated, "
          f"cross-process trace {tid} merged "
          f"({merged['spans']} spans), disabled tracing "
          f"{per_call * 1e9:.0f}ns/call -> {outdir}")
    return 0


if __name__ == "__main__":
    argv = sys.argv[1:]
    fleet = "--fleet" in argv
    argv = [a for a in argv if a != "--fleet"]
    sys.exit(fleet_main(*argv) if fleet else main(*argv))
