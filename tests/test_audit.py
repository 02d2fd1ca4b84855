"""Stream-integrity auditor (ISSUE 19): every token stream carries a
verifiable blake2b chain, and the fleet proves its own determinism.

Contract under test: the chain folds (nonce, position, token) into
every link, so two chains agree iff the streams are identical and the
first divergent link IS the first wrong token; the drift table counts
verdicts per scope/kind, mints its counters at FIRST record
(hole-not-zero federation), serves /driftz, and fires ONE flight dump
per process on divergence; the engine returns stream_digest/knobs in
result dicts with the audit flag ON and adds NOTHING — zero result
keys, zero compiled ops — with it OFF; router-side verification files
failover / migration / shadow verdicts; fleet federation reads a
never-armed replica as a HOLE, never a clean zero."""

import glob
import json
import threading
import types
import urllib.error
import urllib.request

import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu.models.gpt import GPTForCausalLM, gpt_config
from paddle_tpu.observability import audit


@pytest.fixture(autouse=True)
def _fresh_audit():
    """Every test starts hole-not-zero (no table, no counters, no
    /driftz provider) with the auditor enabled, and leaves the
    module in the same state for its neighbors."""
    audit.reset()
    audit.enable()
    yield
    audit.reset()
    audit.enable()


# ---------------------------------------------------------------------------
# chain math
# ---------------------------------------------------------------------------


def test_extend_is_deterministic_and_input_sensitive():
    base = audit.extend(b"", 7, 0, 42)
    assert base == audit.extend(b"", 7, 0, 42)
    assert len(base) == audit.DIGEST_SIZE
    # every folded field matters — nonce, position, token, prior chain
    assert base != audit.extend(b"", 8, 0, 42)
    assert base != audit.extend(b"", 7, 1, 42)
    assert base != audit.extend(b"", 7, 0, 43)
    assert base != audit.extend(b"x", 7, 0, 42)


def test_chain_of_matches_incremental_extends_and_heads():
    nonce, toks = 1234, [5, 9, 2, 2, 7]
    chain = b""
    for i, t in enumerate(toks):
        chain = audit.extend(chain, nonce, i, t)
    assert audit.chain_of(nonce, toks) == chain
    heads = audit.heads_of(nonce, toks)
    assert len(heads) == len(toks)
    for i in range(len(toks)):
        assert heads[i] == audit.chain_of(nonce, toks[:i + 1])
    # suffix folding on top of an existing head (the engine's
    # incremental path) reaches the same final chain
    assert audit.chain_of(nonce, toks[2:], chain=heads[1],
                          start=2) == chain
    # empty stream's head is the genesis
    assert audit.chain_of(nonce, []) == b""


def test_verify_prefix_accepts_exact_prefix_only():
    nonce, toks = 55, [3, 1, 4, 1, 5]
    for p in range(len(toks) + 1):
        head = audit.chain_of(nonce, toks[:p])
        assert audit.verify_prefix(nonce, toks, head, p)
    # one flipped token in the claimed prefix breaks it
    bad = audit.chain_of(nonce, [3, 1, 9])
    assert not audit.verify_prefix(nonce, toks, bad, 3)
    # prefix longer than the stream can never verify
    assert not audit.verify_prefix(nonce, toks,
                                   audit.chain_of(nonce, toks), 6)
    assert not audit.verify_prefix(nonce, toks, b"", -1)


def test_first_divergence_names_the_first_wrong_token():
    assert audit.first_divergence([1, 2, 3], [1, 2, 3]) is None
    assert audit.first_divergence([1, 2, 3], [1, 9, 3]) == 1
    assert audit.first_divergence([9, 2], [1, 2]) == 0
    # a pure length difference diverges at the shorter stream's end
    assert audit.first_divergence([1, 2, 3], [1, 2]) == 2
    assert audit.first_divergence([], [4]) == 0


def test_sampled_is_deterministic_and_tracks_the_rate():
    assert not audit.sampled(1, 0.0)
    assert audit.sampled(1, 1.0)
    # pure hash of the nonce: a replayed fleet shadows the SAME set
    picks = [audit.sampled(n, 0.25) for n in range(2000)]
    assert picks == [audit.sampled(n, 0.25) for n in range(2000)]
    frac = sum(picks) / len(picks)
    assert 0.15 < frac < 0.35, frac


# ---------------------------------------------------------------------------
# the drift table: verdicts, lazy mint, /driftz, one-shot dump
# ---------------------------------------------------------------------------


def test_drift_table_counts_verdicts_per_scope_and_kind():
    assert audit.record("a", "failover", True) is None
    assert audit.record("a", "shadow", True) is None
    div = audit.record("b", "migration", False, position=0,
                       chain_ours=b"\x01" * 16, chain_theirs=b"\x02" * 16,
                       nonce=9, knobs_ours={"kv_dtype": "bf16"},
                       knobs_theirs={"kv_dtype": "int8"},
                       detail="mismatched sibling")
    assert div is not None and div["position"] == 0
    pz = audit.driftz_payload()
    assert pz["totals"] == {"verified": 2, "diverged": 1}
    assert pz["scopes"]["a"]["verified"] == 2
    assert pz["scopes"]["b"]["by_kind"]["migration"] == 1
    last = pz["scopes"]["b"]["last_divergence"]
    assert last["chain_ours"] == "01" * 16
    assert last["chain_theirs"] == "02" * 16
    assert last["knobs_theirs"] == {"kv_dtype": "int8"}
    assert audit.instance().counts() == {"verified": 2, "diverged": 1}
    with pytest.raises(ValueError, match="unknown drift kind"):
        audit.record("a", "gossip", True)


def test_metrics_and_driftz_mint_at_first_record_hole_not_zero():
    from paddle_tpu.observability import server as dbg
    from paddle_tpu.observability.metrics import default_registry
    srv = dbg.DebugServer(port=0).start()
    try:
        base = f"http://127.0.0.1:{srv.port}"
        # BEFORE the first record: no drift_* families (line-anchored
        # — fleet_drift_* minted by other tests contains the name as
        # a substring) and /driftz 404s — the federation hole
        with urllib.request.urlopen(base + "/metrics",
                                    timeout=30) as r:
            text = r.read().decode()
        for line in text.splitlines():
            assert not line.startswith(("drift_verified_total",
                                        "drift_divergence_total"))
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(base + "/driftz", timeout=30)
        assert ei.value.code == 404
        # first record arms everything
        audit.record("engine", "shadow", True)
        audit.record("engine", "shadow", False, position=2)
        with urllib.request.urlopen(base + "/driftz", timeout=30) as r:
            dz = json.loads(r.read())
        pz = dz["drift"]["audit"]
        assert pz["enabled"] is True
        assert pz["kinds"] == list(audit.KINDS)
        assert pz["totals"] == {"verified": 1, "diverged": 1}
        with urllib.request.urlopen(base + "/metrics",
                                    timeout=30) as r:
            text = r.read().decode()
        assert "drift_verified_total 1" in text
        assert 'drift_divergence_total{kind="shadow"} 1' in text
        # reset restores the hole (the fixture relies on this too)
        audit.reset()
        fams = {f.name for f in default_registry().families()}
        assert "drift_verified_total" not in fams
        assert "drift_divergence_total" not in fams
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(base + "/driftz", timeout=30)
        assert ei.value.code == 404
    finally:
        srv.stop()


def test_divergence_fires_one_flight_dump_per_process(tmp_path):
    from paddle_tpu.observability.flight import FlightRecorder
    rec = FlightRecorder(str(tmp_path)).install()
    try:
        audit.record("r", "shadow", False, position=3,
                     chain_ours=b"\xaa" * 16, chain_theirs=b"\xbb" * 16,
                     nonce=77, knobs_ours={"kv_dtype": "bf16"})
        audit.record("r", "failover", False, position=0)  # the storm
        dumps = glob.glob(str(tmp_path / "*stream_divergence*"))
        assert len(dumps) == 1, dumps
        rows = [json.loads(x) for x in
                open(dumps[0]).read().splitlines()]
        extra = next(r for r in rows if r.get("kind") == "extra")
        # nested under "divergence" so the record's own claim kind
        # cannot shadow the dump row's kind="extra" tag
        div = extra["divergence"]
        assert div["position"] == 3 and div["kind"] == "shadow"
        assert div["chain_ours"] == "aa" * 16
        assert div["chain_theirs"] == "bb" * 16
        assert div["knobs_ours"] == {"kv_dtype": "bf16"}
    finally:
        rec.uninstall()


# ---------------------------------------------------------------------------
# engine integration: digest in results, disabled adds NOTHING
# ---------------------------------------------------------------------------


def _tiny_engine():
    from paddle_tpu.inference.llm import LLMEngine
    pt.seed(0)
    cfg = gpt_config("gpt2-small", num_layers=2, hidden_size=64,
                     num_heads=4, vocab_size=97,
                     max_position_embeddings=96, hidden_dropout=0.0,
                     attention_dropout=0.0)
    return LLMEngine(GPTForCausalLM(cfg), max_seqs=2, page_size=4,
                     num_pages=32, prefill_chunk=16, seed=0)


def test_engine_result_digest_is_the_chain_of_its_stream():
    eng = _tiny_engine()
    with eng:
        out = eng.submit([4, 5, 6], max_new_tokens=4,
                         temperature=0.8).result(timeout=300)
    assert out["stream_digest"] == \
        audit.chain_of(out["nonce"], out["output_ids"]).hex()
    assert set(out["knobs"]) == {"kv_dtype", "spec_k", "draft"}


def test_disabled_audit_adds_no_result_keys_and_no_ops():
    """Disabled cost is one module-flag check: the result dict gains
    no audit keys, and the compiled decode program is byte-identical
    to an audit-enabled engine's (the chain is pure host arithmetic
    — the HLO pin that keeps it off the device forever)."""
    def tick_hlo(eng):
        b = eng.max_seqs
        zeros = np.zeros((b,), np.int32)
        return eng._decode_fn.lower(
            eng._params, eng._buffers, jnp.asarray(zeros),
            eng._stage_decode(zeros, zeros), eng.k_pages, eng.v_pages,
            eng._key).as_text()

    eng_on = _tiny_engine()
    with eng_on:
        on = eng_on.submit([1, 2, 3],
                           max_new_tokens=3).result(timeout=300)
        hlo_on = tick_hlo(eng_on)
    assert "stream_digest" in on
    audit.disable()
    try:
        eng_off = _tiny_engine()
        with eng_off:
            off = eng_off.submit([1, 2, 3],
                                 max_new_tokens=3).result(timeout=300)
            hlo_off = tick_hlo(eng_off)
        assert "stream_digest" not in off
        assert "knobs" not in off
        assert off["output_ids"] == on["output_ids"]
    finally:
        audit.enable()
    assert hlo_on == hlo_off, \
        "the audit flag changed a compiled program"
    # nothing was recorded either way: no claim, no verdict
    assert audit.instance().counts() == {"verified": 0, "diverged": 0}


# ---------------------------------------------------------------------------
# router verdicts: failover / migration / shadow
# ---------------------------------------------------------------------------


def _stub_router():
    """The slice of Router state _verify_stream/_shadow touch —
    verdict logic under test without spinning replicas (chaos_soak's
    drift storm exercises the full stack)."""
    from paddle_tpu.serving.router import Router
    stub = types.SimpleNamespace(
        name="router", _mu=threading.Lock(), _knobs={}, n_shadows=0,
        _pool=None)
    stub.verify = lambda req, st, out: Router._verify_stream(
        stub, req, st, out)
    stub.shadow = lambda req, st, out: Router._shadow(
        stub, req, st, out)
    return stub


def _req(nonce, *, failovers=0, migrate=None, prior_knobs=None):
    return types.SimpleNamespace(
        nonce=nonce, failovers=failovers, migrate=migrate,
        prior_knobs=prior_knobs, prompt=[1, 2], max_new_tokens=4,
        temperature=0.0)


def _out(nonce, tokens, knobs=None):
    return {"output_ids": list(tokens),
            "stream_digest": audit.chain_of(nonce, tokens).hex(),
            "knobs": knobs or {"kv_dtype": "bf16"}}


def test_router_failover_verdicts():
    r = _stub_router()
    st = types.SimpleNamespace(name="b")
    knobs = {"kv_dtype": "bf16", "spec_k": 0}
    # intact chain + matching sibling knobs -> verified
    r.verify(_req(1, failovers=1, prior_knobs=knobs), st,
             _out(1, [7, 8, 9], knobs))
    assert audit.instance().counts() == {"verified": 1, "diverged": 0}
    # a sibling serving under DIFFERENT knobs is a detected drift
    r.verify(_req(2, failovers=1,
                  prior_knobs={"kv_dtype": "int8", "spec_k": 0}),
             st, _out(2, [7, 8], knobs))
    # a digest that does not match the returned tokens is corruption
    bad = _out(3, [4, 5, 6], knobs)
    bad["stream_digest"] = audit.chain_of(3, [4, 5, 9]).hex()
    r.verify(_req(3, failovers=1, prior_knobs=knobs), st, bad)
    pz = audit.driftz_payload()
    assert pz["scopes"]["router"]["by_kind"]["failover"] == 2
    assert pz["scopes"]["router"]["last_divergence"]["position"] == 3
    # no failover claimed, no verdict filed (shadows own that case)
    r.verify(_req(4), st, _out(4, [1, 1]))
    assert audit.instance().counts()["verified"] == 1


def test_router_migration_fill_witness_verdicts():
    r = _stub_router()
    st = types.SimpleNamespace(name="decode0")
    toks = [11, 12, 13]
    fill_ok = audit.chain_of(5, toks[:1]).hex()
    r.verify(_req(5, migrate={"fill_digest": fill_ok,
                              "prefill": "p0"}), st, _out(5, toks))
    assert audit.instance().counts() == {"verified": 1, "diverged": 0}
    # a fill emitted under drifted pages names position 0
    fill_bad = audit.chain_of(6, [99]).hex()
    r.verify(_req(6, migrate={"fill_digest": fill_bad,
                              "prefill": "p0"}), st, _out(6, toks))
    last = audit.driftz_payload()["scopes"]["router"]["last_divergence"]
    assert last["kind"] == "migration" and last["position"] == 0


def test_router_shadow_reexecution_verdicts():
    r = _stub_router()
    served = _out(9, [3, 4, 5, 6])
    agree = types.SimpleNamespace(
        name="a", client=types.SimpleNamespace(
            submit=lambda *a, **k: _out(9, [3, 4, 5, 6])))
    r.shadow(_req(9), agree, dict(served))
    assert audit.instance().counts() == {"verified": 1, "diverged": 0}
    differ = types.SimpleNamespace(
        name="a", client=types.SimpleNamespace(
            submit=lambda *a, **k: _out(9, [3, 4, 1, 6])))
    r.shadow(_req(9), differ, dict(served))
    last = audit.driftz_payload()["scopes"]["router"]["last_divergence"]
    assert last["kind"] == "shadow" and last["position"] == 2
    assert last["chain_ours"] == served["stream_digest"]


# ---------------------------------------------------------------------------
# fleet federation: hole-not-zero
# ---------------------------------------------------------------------------


def test_fleet_drift_federation_reads_never_armed_as_a_hole():
    from paddle_tpu.observability.metrics import MetricRegistry
    from paddle_tpu.serving.fleet import FleetScraper
    fs = FleetScraper(registry=MetricRegistry())
    # nobody armed: sums are None (unverified != verified-clean)
    fs.record("hole", "llm_requests_completed 3\n")
    agg = fs.aggregates()
    assert agg["drift_verified"] is None
    assert agg["drift_divergences"] is None
    assert agg["drift_replicas"] == 0
    # one armed replica enters; the hole stays out of the denominator
    fs.record("armed", "drift_verified_total 5\n"
                       'drift_divergence_total{kind="shadow"} 1\n'
                       'drift_divergence_total{kind="failover"} 2\n')
    agg = fs.aggregates()
    assert agg["drift_verified"] == 5
    assert agg["drift_divergences"] == 3   # every {kind} sample summed
    assert agg["drift_replicas"] == 1
    # the armed replica's series federate; the hole exports none
    text = fs.render_prometheus()
    assert 'fleet_drift_verified_total{replica="armed"} 5.0' in text
    assert ('fleet_drift_divergence_total'
            '{replica="armed",kind="shadow"} 1.0') in text
    assert not any("drift_" in ln for ln in text.splitlines()
                   if 'replica="hole"' in ln)
