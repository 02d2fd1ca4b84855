"""models/kimi_linear.py against the plain reference
(benchmark/reference/kimi_linear.py: float32, the delta rule token by token,
MLA expanded over the whole sequence, no pages) on seeded weights, at a small
size on the CPU (5 layers: a dense KDA layer, two routed KDA layers, a routed
MLA layer, a routed KDA layer; 2 KDA heads of 16 with a conv of 4; 4 MLA
heads of 16 + 8 / 16 over a latent of 32; 8 experts, 2 a token): the
whole-sequence forward, and the served path through ``LLMEngine``: prompts in
chunks (chunk form of the delta rule, query tiles or the gathered path over
the latent pages, absorbed), then decode.

Tolerances: float32 throughout, so what separates the program from the
reference is the order of float32 sums (the chunk form's triangular inverse
against a token loop, absorbed against expanded attention): with the seeded
matrices scaled by 8 the logits reach 5 and agree to TOL = 1e-4 (1e-5
read). A bfloat16 ``log a`` moves them by 1e-2 and a bfloat16 state by 2
(asserted below), so the tolerance tells them apart by a factor of 100."""
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from benchmark import weights_kda
from benchmark.reference import kimi_linear as ref
from paddle_tpu.inference.llm import (CacheGroupUnsupported, LLMEngine,
                                      RecurrentStateUnsupported)
from paddle_tpu.models import KimiLinearConfig, KimiLinearForCausalLM
from paddle_tpu.models.gpt import GPTForCausalLM, gpt_config
from paddle_tpu.observability import server as dbgsrv

TOL = 1e-4
TINY = dict(
    vocab_size=128, hidden_size=64, intermediate_size=96,
    num_hidden_layers=5, num_attention_heads=4, kv_lora_rank=32,
    qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
    linear_attn_config={"kda_layers": [1, 2, 3, 5], "full_attn_layers": [4],
                        "num_heads": 2, "head_dim": 16,
                        "short_conv_kernel_size": 4},
    first_k_dense_replace=1, moe_intermediate_size=32, num_experts=8,
    num_experts_per_token=2, num_shared_experts=1,
    routed_scaling_factor=2.446, rms_norm_eps=1e-5, decay_rank=8,
    gate_rank=8)
ENGINE = dict(page_size=8, num_pages=64, max_len=128, prefill_chunk=16,
              kv_dtype="f32")
LATENT_ONLY = {"kda_layers": [], "full_attn_layers": [1, 2, 3, 4, 5],
               "num_heads": 2, "head_dim": 16, "short_conv_kernel_size": 4}


def build(seed=5, **over):
    """``(net, params, dims)`` around the benchmark's seeded arrays, the
    matrices times 8 (at std 0.02 and this width every logit is ~1e-3)."""
    model = dict(TINY, **over)
    d = weights_kda.dims_of(model)
    params = {k: v * 8 if v.ndim >= 2 and "conv" not in k else v
              for k, v in weights_kda.make(d, seed, jnp.float32).items()}
    pt.seed(0)
    net = KimiLinearForCausalLM(KimiLinearConfig(**model,
                                                 model_max_length=256))
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
    return build()


def test_whole_sequence_forward_matches_the_reference(model):
    """57 positions: the chunk form over four blocks of 16 against the
    reference's token loop; expanded MLA against expanded MLA."""
    net, params, d = model
    ids = jnp.asarray(np.random.default_rng(0).integers(0, 128, (2, 57)),
                      jnp.int32)
    want = ref.logits(params, ids, d)
    assert float(jnp.max(jnp.abs(want))) > 2.0
    np.testing.assert_allclose(net(ids), want, atol=TOL, rtol=TOL)


def test_a_lower_precision_misses_the_tolerance(model):
    _, params, d = model
    ids = jnp.asarray(np.random.default_rng(0).integers(0, 128, (1, 37)),
                      jnp.int32)
    want = ref.logits(params, ids, d)
    for quant in ("bf16", "fp8", "state_bf16", "decay_bf16"):
        moved = float(jnp.max(jnp.abs(ref.logits(params, ids, d, quant)
                                      - want)))
        assert moved > 50 * TOL, (quant, moved)


def test_one_latent_group_and_one_state_row_a_kda_layer(model):
    net, _, _ = model
    (group,) = net.kv_cache_spec()
    # a row of 32 + 8 values stored in 128 lanes, its first 32 the value
    assert tuple(group)[:6] == ("latent", 1, 1, 128, None, 32)
    # (PR 42's two fields: no V of its own width, no sink)
    assert (group.v_head_dim, group.sink) == (None, False)
    spec = net.state_cache_spec()
    assert (spec["layers"], spec["conv_state"], spec["ssm_state"],
            spec["impls"], spec["chunk_impls"]) == (
        4, (3, 96), (2, 16, 16), ("xla", "pallas"), ("xla",))
    assert net.moe_aux_spec() == (4, 8) and net.experts_held == (0, 8)


def test_absorbed_attention_is_the_expanded(model):
    """One MLA layer alone: its rows written to a latent pool and attended
    absorbed (``q~`` against the rows as they lie, ``W^V`` after the sum)
    against the layer's whole-sequence forward, which expands K and V."""
    from paddle_tpu.ops.paged_attention import (kv_write,
                                                ragged_paged_attention)
    net, _, _ = model
    mixer = net.layers[3].mixer
    s, ps = 21, 8
    u = jnp.asarray(np.random.default_rng(1).normal(size=(s, 64)),
                    jnp.float32)
    row = jnp.pad(mixer.latent(u), ((0, 0), (0, 128 - 40)))
    pos = jnp.arange(s)
    table = jnp.asarray([[3, 1, 2]])
    pool = kv_write(jnp.zeros((1, 4, ps, 128)), 0, table[0, pos // ps],
                    pos % ps, row)
    att = ragged_paged_attention(
        mixer.absorb(mixer.queries(u)), pool, None,
        jnp.repeat(table, s, 0), pos + 1, scale=mixer.scale, layer=0,
        value_dim=32)
    np.testing.assert_allclose(mixer.project(att), mixer(u), atol=2e-5,
                               rtol=2e-5)


@pytest.mark.parametrize("knobs", [dict(), dict(max_seqs=1),
                                   dict(attention_impl="pallas"),
                                   dict(decode_ticks_per_dispatch=2)],
                         ids=["mixed_ticks", "one_slot", "kernel",
                              "fused_slab"])
def test_engine_holds_to_the_reference_through_chunks_and_decode(model,
                                                                 knobs):
    """Prompts of less and more than a chunk that share chunks and join at
    different times (3 slots, 5 requests: a slot's state row and pages are
    reused by a later request), then 24 tokens of decode. Every served
    token's LOGIT within TOL of the reference's best, and the tokens those
    of ``generate`` (the whole-sequence forward). ``kernel``: the latent
    pages through the row walk and the query tiles, interpreted.
    ``fused_slab``: the scan's carry takes a pool without V pages as it
    takes any other. ``one_slot``: all five through ONE slot in turn, its
    state row restarted and its latent pages handed to the next."""
    net, params, d = model
    prompts = prompts_of((70, 45, 9, 30, 61))
    with LLMEngine(net, **{"max_seqs": 3, **ENGINE, **knobs}) as eng:
        # the spec names the step's kernel too; the platform decides, and
        # an engine off the TPU stays on ``kda_step``
        assert eng.state_impl == "xla"
        futs = [eng.submit(p, max_new_tokens=24) for p in prompts[:4]]
        outs = [f.result(timeout=900) for f in futs]
        outs.append(eng.submit(prompts[4], max_new_tokens=24)
                    .result(timeout=900))
        (latent,) = eng._pool.groups
        assert latent.v_pages is None and eng.v_pages == (None,)
        assert latent.k_pages.shape == (1, 64, 8, 128)
        assert latent.page_bytes == 8 * 128 * 4
        assert len(latent.free) == latent.num_pages - 1
        assert eng.moe_rows_by_expert.shape == (4, 8)
    for p, o in zip(prompts, outs):
        toks = list(o["output_ids"])
        assert len(toks) == 24 and not o["truncated"]
        assert served_gap(params, d, p, toks) <= TOL
        want = np.asarray(net.generate(jnp.asarray([p], jnp.int32), 24))
        assert toks == want[0, len(p):].tolist()


@pytest.mark.parametrize("knobs", [dict(), dict(max_seqs=1),
                                   dict(decode_ticks_per_dispatch=2)],
                         ids=["mixed_and_decode_ticks", "one_slot",
                              "fused_slab"])
def test_the_step_kernel_serves_what_kda_step_serves(model, knobs,
                                                     monkeypatch):
    """What an engine runs on a TPU (the spec names the kernel), here
    through the Pallas interpreter: the decode rows' state stepped in
    place by ``kda_step_kernel`` (a decay a channel; live rows only; 3
    slots and 5 requests, so rows stand empty and are restarted) serves
    the tokens ``kda_step`` serves, each logit within TOL of the
    reference's best. Off the TPU an engine takes ``kda_step``; the test,
    not an option, steers it. While tracing, a decode dispatch's
    ``state_bytes`` count its LIVE rows' ``ssm_state`` and a mixed one's
    chunk half the 8 rows a gathered chunk moves."""
    from paddle_tpu.inference import llm
    from paddle_tpu.observability import tracing
    net, params, d = model
    prompts = prompts_of((70, 45, 9, 30, 61))
    monkeypatch.setattr(llm, "_state_impl",
                        lambda ssm_state, impls=None: "pallas")
    was = tracing.enabled()
    tracing.enable()
    tracing.clear()
    try:
        with LLMEngine(net, **{"max_seqs": 3, **ENGINE, **knobs}) as eng:
            assert eng.state_impl == "pallas" and not eng._chunk_in_place
            futs = [eng.submit(p, max_new_tokens=24) for p in prompts[:4]]
            outs = [f.result(timeout=900) for f in futs]
            outs.append(eng.submit(prompts[4], max_new_tokens=24)
                        .result(timeout=900))
            row, slots = eng._state_row_bytes, eng.max_seqs
        spans = tracing.finished_spans()
    finally:
        (tracing.enable if was else tracing.disable)()
    for p, o in zip(prompts, outs):
        toks = list(o["output_ids"])
        assert served_gap(params, d, p, toks) <= TOL
        want = np.asarray(net.generate(jnp.asarray([p], jnp.int32), 24))
        assert toks == want[0, len(p):].tolist()
    issued = [s for s in spans if s["name"].startswith("llm.issue.")
              and "state_bytes" in s["attrs"]]
    assert issued
    for s in issued:
        a = s["attrs"]
        seqs = a.get("chunk_rows", 0)
        chunk = 8 if seqs else 0
        assert a["state_bytes"] == 2 * (
            (slots + chunk) * row["conv_state"]
            + (a["state_rows"] - seqs + chunk) * row["ssm_state"]), s["name"]


def test_a_sequences_state_and_pages_are_untouched_by_its_neighbours(model):
    """The same request alone and between two others that start before and
    after it: the same tokens, and the same logits' gap to the reference."""
    net, params, d = model
    mine, before, after = prompts_of((40, 23, 58), seed=3)
    with LLMEngine(net, max_seqs=3, **ENGINE) as eng:
        alone = eng.submit(mine, max_new_tokens=16).result(timeout=600)
        futs = [eng.submit(before, max_new_tokens=30),
                eng.submit(mine, max_new_tokens=16),
                eng.submit(after, max_new_tokens=8)]
        crowd = [f.result(timeout=600) for f in futs]
    assert list(crowd[1]["output_ids"]) == list(alone["output_ids"])
    for p, o in zip((before, mine, after), crowd):
        assert served_gap(params, d, p, list(o["output_ids"])) <= TOL


def test_engine_serves_a_share_of_the_experts():
    """Experts 2-5 of 8 held: the engine against the reference's same
    share, and the counters say what fell on the held ones."""
    net, params, d = build(experts_held=(2, 4))
    assert net.experts_held == (2, 4)
    prompts = prompts_of((33, 12), seed=2)
    with LLMEngine(net, max_seqs=2, **ENGINE) as eng:
        outs = [f.result(timeout=600) for f in
                [eng.submit(p, max_new_tokens=12) for p in prompts]]
        assert eng.moe_rows_by_expert.shape == (4, 4)
        assert 0 < eng.n_moe_pairs_held < eng.n_moe_pairs
    for p, o in zip(prompts, outs):
        assert served_gap(params, d, p, list(o["output_ids"])) <= TOL


def _draft():
    pt.seed(0)
    return GPTForCausalLM(gpt_config("gpt2-small", num_layers=1,
                                     hidden_size=32, num_heads=2,
                                     vocab_size=128))


def test_what_the_state_does_not_compose_with_is_refused_by_name(model):
    net, _, _ = model
    # (the latent group's refusal stands in front of the state's)
    with pytest.raises(CacheGroupUnsupported) as e:
        LLMEngine(net, max_seqs=2, **ENGINE, draft_net=_draft())
    assert e.value.mechanism == "speculative_verify"
    with pytest.raises(CacheGroupUnsupported) as e:
        LLMEngine(net, max_seqs=2, **dict(ENGINE, kv_dtype="int8"))
    assert e.value.mechanism == "int8_pages"
    with LLMEngine(net, max_seqs=2, **ENGINE, prefix_cache=True) as eng:
        assert eng._cache is None
        for call in (lambda: eng.export_pages([]),
                     lambda: eng.import_pages({})):
            with pytest.raises(RecurrentStateUnsupported) as e:
                call()
            assert e.value.mechanism == "kv_page_migration"
        status = dbgsrv._collect_status()[eng._status_name]
        (group,) = status["cache_groups"]
        assert (group["name"], group["layers"], group["value_dim"],
                group["row_bytes"], group["page_bytes"]) == (
            "latent", 1, 32, 128 * 4, 8 * 128 * 4)
        state = status["recurrent_state"]
        # (the platform decides: "xla" off the TPU)
        assert state["state_impl"] == "xla" and state["rows"] == 3
        assert state["row_bytes"] == {"conv_state": 4 * 3 * 96 * 4,
                                      "ssm_state": 4 * 2 * 16 * 16 * 4}
        assert set(status["cache_groups_unsupported"]) == {
            "prefix_reuse", "kv_page_migration", "speculative_verify",
            "int8_pages"}
        assert status["prefix_cache"]["enabled"] is False
        assert status["moe"]["moe_impl"] == "xla"


def test_what_a_latent_group_does_not_compose_with_is_refused_by_name():
    """A stack of MLA layers alone: no state, so the refusals are the
    latent group's own."""
    net, params, d = build(linear_attn_config=LATENT_ONLY)
    assert net.state_cache_spec() is None
    assert net.kv_cache_spec()[0].layers == 5
    for knobs, mechanism in ((dict(draft_net=_draft()),
                              "speculative_verify"),
                             (dict(kv_dtype="int8"), "int8_pages")):
        with pytest.raises(CacheGroupUnsupported) as e:
            LLMEngine(net, max_seqs=2, **dict(ENGINE, **knobs))
        assert e.value.mechanism == mechanism
    prompt = prompts_of((37,), seed=4)[0]
    with LLMEngine(net, max_seqs=2, **ENGINE, prefix_cache=True) as eng:
        assert eng._cache is None and eng.conv_state is None
        for call in (lambda: eng.export_pages([]),
                     lambda: eng.import_pages({})):
            with pytest.raises(CacheGroupUnsupported) as e:
                call()
            assert e.value.mechanism == "kv_page_migration"
        status = dbgsrv._collect_status()[eng._status_name]
        assert status["prefix_cache"]["enabled"] is False \
            and "latent" in status["prefix_cache"]["reason"]
        out = eng.submit(prompt, max_new_tokens=10).result(timeout=600)
    assert served_gap(params, d, prompt, list(out["output_ids"])) <= TOL


def test_the_issue_phases_say_what_the_state_and_the_latent_group_moved(
        model):
    """While tracing: ``kv_groups`` names the latent group (what a decode
    tick read and keeps live at ITS page's bytes, what the slots hold),
    ``state_rows`` / ``state_bytes`` the rows the tick advanced, and the
    drain's phase the experts the routing touched."""
    from paddle_tpu.observability import tracing
    net, _, _ = model
    tracing.enable()
    try:
        tracing.clear()
        with LLMEngine(net, max_seqs=2, **ENGINE,
                       attention_impl="pallas") as eng:
            eng.submit(prompts_of((50,))[0], max_new_tokens=12) \
                .result(timeout=600)
            page_bytes = eng._pool.groups[0].page_bytes
            row = eng._state_row_bytes
        spans = tracing.finished_spans()
    finally:
        tracing.disable()
    decode = [s for s in spans if s["name"] == "llm.issue.decode"][-1]
    a = decode["attrs"]
    latent = a["kv_groups"]["latent"]
    assert latent["read"] == latent["live"] == a["kv_pages_read"] \
        == -(-a["context_tokens"] // 8)
    assert latent["page_bytes"] == page_bytes == 8 * 128 * 4
    assert latent["bytes_held"] == latent["live"] * page_bytes
    assert a["state_rows"] == 1
    # the plain step reads and writes every slot's row
    assert a["state_bytes"] == 2 * 2 * (row["conv_state"]
                                        + row["ssm_state"])
    emit = [s for s in spans if s["name"] == "llm.drain.emit"
            and "experts_touched" in s["attrs"]]
    assert emit and 0 < emit[-1]["attrs"]["experts_touched"] <= 4 * 2
    assert emit[-1]["attrs"]["moe_impl"] == "xla"


def test_config_refuses_what_the_model_does_not_compute():
    for over in (dict(q_lora_rank=64), dict(mla_use_nope=False),
                 dict(moe_router_activation_func="softmax"),
                 dict(moe_renormalize=False)):
        with pytest.raises(NotImplementedError):
            KimiLinearConfig(**dict(TINY, **over))
    with pytest.raises(ValueError, match="no kind for layer 5"):
        KimiLinearConfig(**dict(TINY, linear_attn_config=dict(
            TINY["linear_attn_config"], kda_layers=[1, 2, 3])))
    full = KimiLinearConfig()
    assert full.layer_kinds.count("kda") == 20 \
        and full.layer_kinds.count("mla") == 7
    assert full.layer_kinds[:4] == ("kda", "kda", "kda", "mla") \
        and full.layer_kinds[-1] == "mla"
    assert (full.latent_dim, full.latent_width) == (576, 640)
