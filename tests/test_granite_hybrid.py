"""models/granite_hybrid.py against the plain reference
(benchmark/reference/granite_hybrid.py: float32, the token-by-token
recurrence) on seeded weights, at a small size on the CPU: the whole-sequence
forward, and the served path through ``LLMEngine`` with mixed ticks.

Tolerances: float32 throughout, so what separates the program from the
reference is the order of float32 sums (the chunked scan against the
recurrence, grouped experts against an expert at a time, paged attention
against a dense softmax): logits of up to 0.02 (this model divides them by
16, and its tied embedding is small) agree to 2e-6; they read 2e-8. The
reference with bfloat16 operands moves them by 9e-4, with float8 by 2.6e-3:
computing in a lower precision would miss the tolerance by two orders of
magnitude and more."""
import json
import urllib.request

import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from benchmark import weights_hybrid
from benchmark.reference import granite_hybrid as ref
from paddle_tpu.inference.llm import (LLMEngine, RecurrentStateUnsupported,
                                      serve_llm)
from paddle_tpu.models import GraniteHybridConfig, GraniteHybridForCausalLM
from paddle_tpu.models.gpt import GPTForCausalLM, gpt_config
from paddle_tpu.observability import server as dbgsrv

TOL = 2e-6
TINY = dict(
    num_layers=4, layer_types=["mamba", "mamba", "attention", "mamba"],
    hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
    mamba_n_heads=8, mamba_d_head=16, mamba_d_state=16, mamba_d_conv=4,
    mamba_n_groups=1, mamba_chunk_size=8, num_local_experts=8,
    num_experts_per_tok=2, intermediate_size=32,
    shared_intermediate_size=48, vocab_size=128, rms_norm_eps=1e-5,
    attention_multiplier=1 / 16, embedding_multiplier=12,
    residual_multiplier=0.22, logits_scaling=16)


def build(held=None, seed=5, scale=6.0):
    """``(net, params, dims)``: the seeded arrays, every matrix scaled up so
    that the mixers move the logits and tokens vary (at std 0.02 and width
    64 the tied head would repeat its input)."""
    model = dict(TINY)
    whole = weights_hybrid.dims_of(model)
    params = {k: (v * scale if v.ndim >= 2 and "conv" not in k else v)
              for k, v in weights_hybrid.make(whole, seed,
                                              jnp.float32).items()}
    d = whole
    if held is not None:
        # a share holds its slice of the SAME experts
        model["experts_held"] = list(held)
        d = weights_hybrid.dims_of(model)
        lo, hi = held[0], held[0] + held[1]
        params = {k: (v[lo:hi] if k.endswith(("moe.w_in", "moe.w_out"))
                      else v) for k, v in params.items()}
    cfg = GraniteHybridConfig(
        **{k: v for k, v in model.items() if k != "num_layers"},
        max_position_embeddings=256)
    pt.seed(0)
    net = GraniteHybridForCausalLM(cfg)
    net.eval()
    assert set(net.state_dict()) == set(params)
    net.set_state_dict(params)
    return net, params, d


def prompts_of(lengths, seed=0):
    r = np.random.default_rng(seed)
    return [list(map(int, r.integers(0, 128, n))) for n in lengths]


def served_gap(params, d, prompt, out):
    """The benchmark's measure: the widest gap by which a served token's
    logit lies below the reference's best, teacher-forced."""
    seq = np.asarray([prompt + out], np.int32)
    n, m = len(prompt), len(out)
    served = np.zeros_like(seq)
    served[0, n - 1:n + m - 1] = out
    got = ref.served_gaps(params, seq, np.asarray([n - 1]), np.asarray([m]),
                          served, d)
    return float(np.max(np.asarray(got["gap"])))


@pytest.fixture(scope="module")
def model():
    return build(held=(0, 4))


def test_whole_sequence_forward_matches_the_reference(model):
    net, params, d = model
    ids = jnp.asarray(np.random.default_rng(0).integers(0, 128, (2, 37)),
                      jnp.int32)
    want = ref.logits(params, ids, d)
    assert float(jnp.max(jnp.abs(want))) > 0.01
    np.testing.assert_allclose(net(ids), want, atol=TOL, rtol=TOL)


def test_the_two_shares_of_a_layer_add_up_to_the_uncut_references_layer():
    """Experts 0-3 and 4-7 of 8, the shared expert counted once: the
    program's two shares of one layer sum to what the reference gives for
    the layer with every expert."""
    whole = build(held=None)
    x = jnp.asarray(np.random.default_rng(3).normal(size=(1, 19, 64)),
                    jnp.float32)
    lp = {k[len("layers.1."):]: v for k, v in whole[1].items()
          if k.startswith("layers.1.")}
    dd = ref._sizes(whole[2])
    v = ref.rms_norm(x, lp["post_norm.weight"], dd["eps"])
    want_routed = ref.routed(v, lp, dd)[0]
    want_shared = ref.gated_mlp(v, lp["shared.w_in.weight"],
                                lp["shared.w_out.weight"])[0]
    routed = 0.0
    for held in ((0, 4), (4, 4)):
        layer = build(held=held)[0].layers[1]
        part, _ = layer.moe(v[0])
        routed = routed + part
        np.testing.assert_allclose(layer.shared(v[0]), want_shared,
                                   atol=TOL, rtol=TOL)
    np.testing.assert_allclose(routed, want_routed, atol=TOL, rtol=TOL)


def test_engine_mixed_ticks_hold_to_the_reference(model):
    """Prompts of different lengths that share chunks and join at
    different times (4 slots, 7 requests, chunk 16), then decode: every
    served token within TOL of the reference's best, and token for token
    what ``generate`` gives."""
    net, params, d = model
    prompts = prompts_of((5, 23, 9, 40, 3, 17, 33))
    with LLMEngine(net, max_seqs=4, page_size=8, num_pages=64, max_len=128,
                   prefill_chunk=16, kv_dtype="f32") as eng:
        futs = [eng.submit(p, max_new_tokens=10) for p in prompts[:5]]
        outs = [f.result(timeout=600) for f in futs]
        futs = [eng.submit(p, max_new_tokens=10) for p in prompts[5:]]
        outs += [f.result(timeout=600) for f in futs]
        assert "m" in eng.tick_history and "d" in eng.tick_history
        assert eng.n_moe_pairs == eng.n_moe_pairs_held * 2 or \
            0 < eng.n_moe_pairs_held < eng.n_moe_pairs
        assert eng.moe_rows_by_expert.shape == (4, 4)
        assert int(eng.moe_rows_by_expert.sum()) == eng.n_moe_pairs_held
    distinct = set()
    for p, o in zip(prompts, outs):
        toks = list(o["output_ids"])
        assert len(toks) == 10
        assert served_gap(params, d, p, toks) <= TOL
        want = np.asarray(net.generate(jnp.asarray([p], jnp.int32), 10))
        assert toks == want[0, len(p):].tolist()
        distinct.update(toks)
    assert len(distinct) > 5


def test_a_reused_slot_serves_what_a_fresh_engine_serves(model):
    """One slot, three requests in turn: the second and third start from a
    row the first left its state in, and from an inactive row's padding."""
    net, params, d = model
    prompts = prompts_of((21, 4, 30), seed=8)
    kw = dict(page_size=8, num_pages=64, max_len=128, prefill_chunk=16,
              kv_dtype="f32")
    with LLMEngine(net, max_seqs=1, **kw) as eng:
        reused = [eng.submit(p, max_new_tokens=8).result(timeout=600)
                  ["output_ids"] for p in prompts]
    for p, got in zip(prompts, reused):
        with LLMEngine(net, max_seqs=3, **kw) as eng:
            fresh = eng.submit(p, max_new_tokens=8).result(timeout=600)
        assert list(got) == list(fresh["output_ids"])
        assert served_gap(params, d, p, list(got)) <= TOL


@pytest.mark.parametrize("knobs", [dict(max_seqs=1),
                                   dict(decode_ticks_per_dispatch=4)],
                         ids=["one_slot", "slab"])
def test_the_other_tick_paths_serve_the_same_tokens(model, knobs):
    """``one_slot``: all prompts through ONE slot in turn: no decode row
    ever beside a prompt row, the state row restarted for each, and the
    state arrays at their smallest (the slot and the scratch row)."""
    net, params, d = model
    prompts = prompts_of((12, 27, 6), seed=4)
    kw = dict(max_seqs=2, page_size=8, num_pages=64, max_len=128,
              prefill_chunk=16, kv_dtype="f32")
    with LLMEngine(net, **kw) as eng:
        want = [f.result(timeout=600)["output_ids"] for f in
                [eng.submit(p, max_new_tokens=9) for p in prompts]]
    with LLMEngine(net, **{**kw, **knobs}) as eng:
        got = [f.result(timeout=600)["output_ids"] for f in
               [eng.submit(p, max_new_tokens=9) for p in prompts]]
    assert [list(g) for g in got] == [list(w) for w in want]


@pytest.mark.parametrize("knobs", [dict(), dict(max_seqs=1),
                                   dict(decode_ticks_per_dispatch=4)],
                         ids=["mixed_and_decode_ticks", "one_slot",
                              "slab"])
def test_the_state_kernel_serves_what_ssd_step_serves(model, knobs,
                                                      monkeypatch):
    """What a TPU's engine runs, here through the Pallas interpreter: the
    decode rows' state stepped in place by ``ssd_step_kernel`` (live rows
    only; 3 slots and 5 requests, so rows stand empty and are reused)
    gives token for token what ``ssd_step`` gives. Off the TPU the engine
    takes ``ssd_step``; the test, not an option, steers it. ``one_slot``:
    the kernel over the smallest state array, the slot and the scratch
    row, restarted for each of the five requests in turn."""
    from paddle_tpu.inference import llm
    net, params, d = model
    prompts = prompts_of((12, 27, 1, 6, 19), seed=4)
    kw = {**dict(max_seqs=3, page_size=8, num_pages=64, max_len=128,
                 prefill_chunk=16, kv_dtype="f32"), **knobs}

    def serve():
        with LLMEngine(net, **kw) as eng:
            futs = [eng.submit(p, max_new_tokens=9) for p in prompts[:2]]
            outs = [f.result(timeout=600)["output_ids"] for f in futs]
            futs = [eng.submit(p, max_new_tokens=9) for p in prompts[2:]]
            outs += [f.result(timeout=600)["output_ids"] for f in futs]
            return eng.state_impl, [list(o) for o in outs]

    plain_impl, want = serve()
    monkeypatch.setattr(llm, "_state_impl", lambda ssm_state, impls=None: "pallas")
    kernel_impl, got = serve()
    assert (plain_impl, kernel_impl) == ("xla", "pallas")
    assert got == want
    for p, toks in zip(prompts, got):
        assert served_gap(params, d, p, toks) <= TOL


@pytest.mark.parametrize("knobs", [dict(), dict(max_seqs=1),
                                   dict(decode_ticks_per_dispatch=4)],
                         ids=["mixed_and_decode_ticks", "one_slot",
                              "slab"])
def test_the_chunk_kernel_serves_what_ssd_chunked_serves(knobs,
                                                         monkeypatch):
    """The chunk half of a TPU's engine, here through the Pallas
    interpreter, on ``rehearsal-tiny-hybrid``'s shapes and engine options
    (4 slots, chunks of 16 rows = the model's ``mamba_chunk_size``): seven
    prompts, so a chunk holds several sequences (lengths 3, 5, 2), a
    prompt runs over three chunks (40 rows) and slots are reused; greedy
    tokens through ``ssd_chunk_kernel`` are those through ``ssd_chunked``.
    The decode half takes the step kernel in both (its own test is above):
    what differs between the two engines is the chunk's scan alone.
    ``one_slot``: a chunk holds ONE sequence's rows and the kernel's state
    array is the slot and the scratch row."""
    from paddle_tpu.inference import llm
    from paddle_tpu.ops import ssd
    with open("benchmark/configs/rehearsal-tiny-hybrid.json") as f:
        conf = json.load(f)
    assert {k: conf[k] for k in ("mamba_n_heads", "mamba_d_head",
                                 "mamba_d_state")} \
        == {k: TINY[k] for k in ("mamba_n_heads", "mamba_d_head",
                                 "mamba_d_state")}
    model = dict(TINY, mamba_chunk_size=conf["mamba_chunk_size"])
    cfg = GraniteHybridConfig(
        **{k: v for k, v in model.items() if k != "num_layers"},
        max_position_embeddings=conf["max_position_embeddings"])
    pt.seed(3)
    net = GraniteHybridForCausalLM(cfg)
    net.eval()
    net.set_state_dict({k: (v * 6.0 if v.ndim >= 2 and "conv" not in k
                            else v) for k, v in net.state_dict().items()})
    prompts = prompts_of((3, 5, 2, 40, 16, 1, 23), seed=8)
    monkeypatch.setattr(llm, "_state_impl", lambda ssm_state, impls=None: "pallas")
    scans = []
    kernel = ssd.ssd_chunk_kernel

    def serve():
        with LLMEngine(net, **{**conf["engine"], **knobs}) as eng:
            assert eng.state_impl == "pallas"
            futs = [eng.submit(p, max_new_tokens=7) for p in prompts]
            return [list(f.result(timeout=600)["output_ids"]) for f in futs]

    def spied(*a, **kw):
        scans.append(a[0].shape[0])
        return kernel(*a, **kw)

    monkeypatch.setattr(ssd, "ssd_chunk_kernel", spied)
    got = serve()
    assert scans and set(scans) == {conf["engine"]["prefill_chunk"]}

    monkeypatch.setattr(ssd, "ssd_chunk_kernel", ssd.ssd_chunk_gathered)
    want = serve()
    assert got == want
    assert len({tuple(w) for w in want}) > 1      # the tokens vary


@pytest.mark.parametrize("knobs", [dict(), dict(max_seqs=1)],
                         ids=["mixed_and_decode_ticks", "one_slot"])
def test_the_grouped_product_kernel_serves_what_ragged_dot_serves(
        model, knobs, monkeypatch):
    """What a TPU's engine runs, here through the Pallas interpreter: every
    routed layer's two grouped products through ``ops/grouped_matmul.py``
    (the decode and the mixed program; ``one_slot``: a mixed program
    whose decode rows are all inactive, then decode programs of one live
    row) give token for token what ``jax.lax.ragged_dot`` gives, and
    ``/statusz`` and the drain phase name which one the programs were
    built with. Off the TPU the engine takes ``ragged_dot``; the test, not
    an option, steers it."""
    from paddle_tpu.inference import llm
    from paddle_tpu.observability import tracing
    net, params, d = model
    prompts = prompts_of((12, 27, 1, 6, 19), seed=4)
    kw = {**dict(max_seqs=3, page_size=8, num_pages=64, max_len=128,
                 prefill_chunk=16, kv_dtype="f32"), **knobs}
    was_trace = tracing.enabled()

    def serve():
        tracing.enable()
        tracing.clear()
        try:
            with LLMEngine(net, **kw) as eng:
                futs = [eng.submit(p, max_new_tokens=9) for p in prompts]
                outs = [list(f.result(timeout=600)["output_ids"])
                        for f in futs]
                status = dbgsrv._collect_status()[eng._status_name]
            named = {s["attrs"]["moe_impl"]
                     for s in tracing.finished_spans()
                     if s["name"] == "llm.drain.emit"}
        finally:
            (tracing.enable if was_trace else tracing.disable)()
        assert named == {eng.moe_impl} == {status["moe"]["moe_impl"]}
        return eng.moe_impl, outs

    plain_impl, want = serve()
    monkeypatch.setattr(llm, "_moe_impl", lambda net: "pallas")
    kernel_impl, got = serve()
    assert (plain_impl, kernel_impl) == ("xla", "pallas")
    assert got == want
    for p, toks in zip(prompts, got):
        assert served_gap(params, d, p, toks) <= TOL


def test_what_assumes_pages_are_the_whole_context_is_refused_by_name(model):
    net, _, _ = model
    draft = GPTForCausalLM(gpt_config("gpt2-small", num_layers=1,
                                      hidden_size=32, num_heads=2,
                                      vocab_size=128))
    with pytest.raises(RecurrentStateUnsupported) as e:
        LLMEngine(net, max_seqs=2, num_pages=16, max_len=64,
                  draft_net=draft)
    assert e.value.mechanism == "speculative_verify"
    with LLMEngine(net, max_seqs=2, num_pages=16, max_len=64,
                   prefix_cache=True, kv_dtype="f32") as eng:
        assert eng._cache is None            # the prefix cache is off
        for call in (lambda: eng.export_pages([]),
                     lambda: eng.import_pages({})):
            with pytest.raises(RecurrentStateUnsupported) as e:
                call()
            assert e.value.mechanism == "kv_page_migration"
        status = dbgsrv._collect_status()[eng._status_name]
    assert status["prefix_cache"]["enabled"] is False
    assert status["recurrent_state"]["rows"] == 3
    assert status["moe"]["pairs_routed"] == 0


def test_served_over_http(model):
    net, params, d = model
    p = prompts_of((14,), seed=2)[0]
    with LLMEngine(net, max_seqs=2, page_size=8, num_pages=32, max_len=64,
                   prefill_chunk=16, kv_dtype="f32") as eng:
        srv = serve_llm(eng)
        try:
            url = "http://%s:%d/generate" % srv.server_address[:2]
            req = urllib.request.Request(
                url, json.dumps({"prompt_ids": p, "max_new_tokens": 6,
                                 "temperature": 0.0}).encode(),
                {"Content-Type": "application/json"})
            out = json.loads(urllib.request.urlopen(req, timeout=300).read())
        finally:
            srv.shutdown()
            srv.server_close()
    assert served_gap(params, d, p, out["output_ids"]) <= TOL


def test_state_and_routing_are_on_the_spans_the_ledger_and_the_metrics(model):
    """What the per-layer metrics read: ``state_rows`` / ``state_bytes`` on
    every ``llm.issue.*`` phase, ``experts_touched`` / ``moe_rows_held`` on
    the ``llm.drain.emit`` phase of the same ``issue_seq``, the
    ``ssm_state`` / ``conv_state`` rows of the memory ledger, the routed-row
    counter and the state-row gauge."""
    from paddle_tpu.observability import memory as memobs
    from paddle_tpu.observability import metrics as obs
    from paddle_tpu.observability import tracing
    net, _, _ = model
    was_mem, was_trace = memobs.enabled(), tracing.enabled()
    memobs.enable()
    tracing.enable()
    tracing.clear()
    reg = obs.default_registry()

    def routed():
        fam = reg.get("llm_moe_rows_routed_total")
        return {} if fam is None else {
            k: float(v.value) for k, v in fam.children().items()} \
            if hasattr(fam, "children") else {}

    try:
        with LLMEngine(net, max_seqs=2, page_size=8, num_pages=32,
                       max_len=64, prefill_chunk=16,
                       kv_dtype="f32") as eng:
            for p in prompts_of((20, 7), seed=6):
                eng.submit(p, max_new_tokens=5).result(timeout=600)
            rows = {r["owner"]: r for r in memobs.instance().rows()
                    if r["owner"] in ("ssm_state", "conv_state")}
            per_row = eng._state_row_bytes
            assert rows["ssm_state"]["bytes"] == 3 * per_row["ssm_state"]
            assert rows["conv_state"]["bytes"] == 3 * per_row["conv_state"]
            assert per_row["ssm_state"] == 3 * 8 * 16 * 16 * 4
            assert reg.get("llm_state_rows_in_use") is not None
            held, pairs = eng.n_moe_pairs_held, eng.n_moe_pairs
        spans = tracing.finished_spans()
    finally:
        (memobs.enable if was_mem else memobs.disable)()
        (tracing.enable if was_trace else tracing.disable)()
    issues = [s for s in spans if s["name"].startswith("llm.issue.")]
    emits = {s["attrs"]["issue_seq"]: s["attrs"] for s in spans
             if s["name"] == "llm.drain.emit"}
    assert issues and all("state_rows" in s["attrs"]
                          and s["attrs"]["state_bytes"] > 0
                          for s in issues)
    mixed = [s["attrs"] for s in issues if s["name"] == "llm.issue.mixed"]
    # a mixed tick steps both slots' rows and gathers a chunk's 8 sequences
    assert mixed[0]["state_bytes"] == 2 * (2 + 8) * sum(per_row.values())
    joined = [emits[s["attrs"]["issue_seq"]] for s in issues
              if s["attrs"]["issue_seq"] in emits]
    assert joined and all("experts_touched" in a for a in joined)
    assert sum(a["moe_rows_held"] for a in emits.values()) == held
    assert 0 < held < pairs


def test_through_the_kernels_state_bytes_counts_the_sequences_present(
        model, monkeypatch):
    """``state_bytes`` of a mixed dispatch on a TPU's engine: the chunk
    half moves the ``ssm_state`` rows of the sequences in its chunk alone
    (``ssd_chunk_kernel``), not the 8 a chunk may hold; the ``conv_state``
    rows are still gathered 8 at a time and stepped a slot at a time."""
    from paddle_tpu.inference import llm
    from paddle_tpu.observability import tracing
    net, _, _ = model
    monkeypatch.setattr(llm, "_state_impl", lambda ssm_state, impls=None: "pallas")
    was = tracing.enabled()
    tracing.enable()
    tracing.clear()
    try:
        with LLMEngine(net, max_seqs=2, page_size=8, num_pages=32,
                       max_len=64, prefill_chunk=16,
                       kv_dtype="f32") as eng:
            # the second prompt arrives while the first decodes
            eng.submit(prompts_of((20,), seed=6)[0],
                       max_new_tokens=12).result(timeout=600)
            futs = [eng.submit(p, max_new_tokens=4)
                    for p in prompts_of((5, 3), seed=7)]
            for f in futs:
                f.result(timeout=600)
            per_row = eng._state_row_bytes
        spans = tracing.finished_spans()
    finally:
        (tracing.enable if was else tracing.disable)()
    mixed = [s["attrs"] for s in spans if s["name"] == "llm.issue.mixed"]
    assert mixed
    for attrs in mixed:
        seqs, rows = attrs["chunk_rows"], attrs["state_rows"]
        assert 1 <= seqs <= 2 and rows >= seqs
        assert attrs["state_bytes"] == 2 * (
            (2 + 8) * per_row["conv_state"] + rows * per_row["ssm_state"])
    # two short prompts shared one chunk
    assert max(a["chunk_rows"] for a in mixed) == 2


def test_a_model_with_state_lanes_stages_its_decode_in_one_transfer(
        model, issue_phases):
    """ISSUE 44: the state rows are device arrays already and follow the
    key; the host arrays in front of them cross as one (five before), a
    mixed dispatch's as the 15 it sends today (the 13 of a pool of one group
    and the two arrays that say which sequence a prompt row belongs to)."""
    from paddle_tpu.observability import tracing
    net, _, _ = model
    tracing.enable()
    with LLMEngine(net, max_seqs=2, page_size=8, num_pages=32, max_len=64,
                   prefill_chunk=16, kv_dtype="f32") as eng, \
            issue_phases.decode_staging(eng) as seen:
        issue_phases.serve(eng, list(zip(prompts_of((20, 7, 5), seed=6),
                                         (5, 4, 6))))
        assert len(eng._state_args()) == 2
        size = eng._decode_layout.size
    assert seen and {n for n, _, _ in seen} == {1}
    assert {staged.shape for _, staged, _ in seen} == {(size,)}
    assert issue_phases.transfers(tracing.finished_spans()) == {
        "llm.issue.decode": {1}, "llm.issue.mixed": {15}}


def test_the_issue_marks_leave_the_state_attrs_where_the_parent_wrote_them(
        model, issue_phases):
    """ISSUE 37: ``packed`` / ``staged`` / ``launched`` / ``booked`` on every
    dispatch, and ``state_rows`` / ``state_bytes`` (stamped at the phase's
    end now) equal to the same run's on the parent's ordering."""
    from paddle_tpu.observability import tracing
    net, _, _ = model
    tracing.enable()
    with LLMEngine(net, max_seqs=2, page_size=8, num_pages=32, max_len=64,
                   prefill_chunk=16, kv_dtype="f32") as eng:
        issue_phases.serve(eng, list(zip(prompts_of((20, 7, 5), seed=6),
                                         (5, 4, 6))))
    spans = tracing.finished_spans()
    issue_phases.check_marks(spans)
    assert any(s["attrs"]["state_bytes"] for s in issue_phases.launched(spans))
    assert issue_phases.digest(spans) == "19499d95a16bde94"
