"""models/olmo_hybrid.py against the plain reference
(benchmark/reference/olmo_hybrid.py: float32, the delta rule token by token,
attention over the whole sequence, no pages) on seeded weights, at a small
size on the CPU (8 layers, two periods of three ``linear_attention`` and one
``full_attention``; the rule's 3 heads of 8 x 16 with a conv of 4 and a write
strength in (0, 2); 6 heads of 8 over 6 K/V heads, which is no multiple of
the sublane tile: the pool stores 8): the whole-sequence forward, and the
served path through ``LLMEngine``: prompts in chunks (the scalar-decay chunk
form, query tiles or the gathered path over pages of 8 stored heads), then
decode through pages and state rows.

Tolerances: float32 throughout, so what separates the program from the
reference is the order of float32 sums (the chunk form's triangular inverse
against a token loop): with the seeded matrices scaled by 8 the logits reach
3 and agree to TOL = 1e-4. A bfloat16 ``log a`` or a state stored bfloat16
moves them past it (asserted below)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from benchmark import weights_olmo
from benchmark.reference import olmo_hybrid as ref
from paddle_tpu.inference.llm import LLMEngine, RecurrentStateUnsupported
from paddle_tpu.models import OlmoHybridConfig, OlmoHybridForCausalLM
from paddle_tpu.models.gpt import GPTForCausalLM, gpt_config
from paddle_tpu.observability import server as dbgsrv
from paddle_tpu.ops import kda

TOL = 1e-4
PERIOD = ["linear_attention"] * 3 + ["full_attention"]
TINY = dict(
    vocab_size=128, hidden_size=48, intermediate_size=96,
    num_hidden_layers=8, num_attention_heads=6, num_key_value_heads=6,
    max_position_embeddings=256, layer_types=PERIOD * 2,
    linear_num_key_heads=3, linear_num_value_heads=3,
    linear_key_head_dim=8, linear_value_head_dim=16,
    linear_conv_kernel_dim=4, linear_allow_neg_eigval=True,
    rope_parameters={"rope_theta": None}, rms_norm_eps=1e-6)
ENGINE = dict(page_size=8, num_pages=64, max_len=128, prefill_chunk=16,
              kv_dtype="f32")


def build(seed=5, **over):
    """``(net, params, dims)`` around the benchmark's seeded arrays, the
    matrices times 8 (at std 0.02 and this width every logit is ~1e-3)."""
    model = dict(TINY, **over)
    d = weights_olmo.dims_of(model)
    params = {k: v * 8 if v.ndim >= 2 and "conv" not in k else v
              for k, v in weights_olmo.make(d, seed, jnp.float32).items()}
    pt.seed(0)
    net = OlmoHybridForCausalLM(OlmoHybridConfig(**model))
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
    """57 positions: the scalar-decay chunk form over four blocks of 16
    against the reference's token loop, write strengths over the whole of
    (0, 2)."""
    net, params, d = model
    ids = jnp.asarray(np.random.default_rng(0).integers(0, 128, (2, 57)),
                      jnp.int32)
    want = ref.logits(params, ids, d)
    assert float(jnp.max(jnp.abs(want))) > 2.0
    np.testing.assert_allclose(net(ids), want, atol=TOL, rtol=TOL)
    # the strengths this model writes with do pass 1
    u = net._embed(ids[0]).astype(jnp.float32)
    _, b = net.layers[0].mixer.gates(u)
    assert float(b.max()) > 1.0 and float(b.min()) > 0.0 \
        and float(b.max()) < 2.0


@pytest.mark.parametrize("quant", ["bf16", "fp8", "state_bf16",
                                   "beta_unscaled", "no_qk_norm"])
def test_a_lower_precision_or_a_fault_misses_the_tolerance(model, quant):
    _, params, d = model
    ids = jnp.asarray(np.random.default_rng(0).integers(0, 128, (1, 37)),
                      jnp.int32)
    want = ref.logits(params, ids, d)
    moved = float(jnp.max(jnp.abs(ref.logits(params, ids, d, quant) - want)))
    assert moved > 50 * TOL, (quant, moved)


@pytest.mark.parametrize("fault", ["log_a_bf16", "state_bf16"])
def test_a_bfloat16_decay_or_stored_state_in_the_program_misses_it(
        model, monkeypatch, fault):
    """The PROGRAM with ``log a`` rounded to bfloat16, or with the state it
    carries between pieces stored bfloat16, against the reference: past
    TOL, so the engine test below would catch either."""
    net, params, d = model
    ids = jnp.asarray(np.random.default_rng(0).integers(0, 128, (1, 57)),
                      jnp.int32)
    want = ref.logits(params, ids, d)
    block = kda._kda_block

    def rounded(q, k, v, log_a, b, oh, state):
        if fault == "log_a_bf16":
            log_a = log_a.astype(jnp.bfloat16).astype(jnp.float32)
        o, new = block(q, k, v, log_a, b, oh, state)
        if fault == "state_bf16":
            new = new.astype(jnp.bfloat16).astype(jnp.float32)
        return o, new

    monkeypatch.setattr(kda, "_kda_block", rounded)
    monkeypatch.setattr(kda, "_PIECE", 16)
    moved = float(jnp.max(jnp.abs(net(ids) - want)))
    assert moved > 10 * TOL, moved


def test_one_group_of_six_heads_stored_as_eight_and_one_state_row_a_rule_layer(
        model):
    net, _, _ = model
    (group,) = net.kv_cache_spec()
    # the group as STORED: six K/V heads in pages of eight
    assert (net.cfg.num_key_value_heads, net.cfg.stored_kv_heads) == (6, 8)
    assert tuple(group)[:4] == ("full", 2, 8, 8)
    assert (group.window, group.value_dim, group.v_head_dim,
            group.sink) == (None, None, None, False)
    spec = net.state_cache_spec()
    # the state as STORED: a head's value of 16 in whole lanes
    assert (spec["layers"], spec["conv_state"], spec["ssm_state"],
            spec["impls"], spec["chunk_impls"], spec["rule"]) == (
        6, (3, 3 * (8 + 8 + 16)), (3, 8, 128), ("xla", "pallas"),
        ("xla",),
        "delta, scalar decay")
    assert net.moe_aux_spec() is None and net.loop_aux_spec() is None


def test_the_published_sizes():
    cfg = OlmoHybridConfig(num_layers=8)
    assert cfg.layer_kinds == tuple(PERIOD * 2)
    assert (cfg.head_dim, cfg.key_inner, cfg.value_inner, cfg.conv_width,
            cfg.value_width) == (128, 2880, 5760, 11520, 256)
    assert OlmoHybridConfig().num_layers == 32


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
    of ``generate`` (the whole-sequence forward). ``kernel``: pages of 8
    stored heads (6 written) through the row walk and the query tiles,
    interpreted."""
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
        (full,) = eng._pool.groups
        assert full.k_pages.shape == full.v_pages.shape == (2, 64, 8, 8, 8)
        # bytes as STORED: eight heads a token, K and V, two layers
        assert full.page_bytes == 2 * 2 * 8 * 8 * 8 * 4
        assert len(full.free) == full.num_pages - 1
        assert eng.ssm_state[0].shape == (eng.max_seqs + 1, 3, 8, 128)
    for p, o in zip(prompts, outs):
        toks = list(o["output_ids"])
        assert len(toks) == 24 and not o["truncated"]
        assert served_gap(params, d, p, toks) <= TOL
        want = np.asarray(net.generate(jnp.asarray([p], jnp.int32), 24))
        assert toks == want[0, len(p):].tolist()


@pytest.mark.parametrize("knobs", [dict(), dict(max_seqs=1)],
                         ids=["mixed_and_decode_ticks", "one_slot"])
def test_the_step_kernel_serves_what_kda_step_serves(model, knobs,
                                                     monkeypatch):
    """What an engine runs on a TPU (the spec names the kernel), here
    through the Pallas interpreter: the decode rows' state (``[3, 8, 128]``
    as stored, ONE decay a head, write strengths in (0, 2)) stepped in
    place by ``kda_step_kernel`` serves the
    tokens ``kda_step`` serves, each logit within TOL of the reference's
    best; 3 slots and 5 requests, so rows stand empty and are restarted.
    Off the TPU an engine takes ``kda_step``; the test, not an option,
    steers it."""
    from paddle_tpu.inference import llm
    net, params, d = model
    prompts = prompts_of((70, 45, 9, 30, 61))
    monkeypatch.setattr(llm, "_state_impl",
                        lambda ssm_state, impls=None: "pallas")
    with LLMEngine(net, **{"max_seqs": 3, **ENGINE, **knobs}) as eng:
        assert eng.state_impl == "pallas" and not eng._chunk_in_place
        futs = [eng.submit(p, max_new_tokens=24) for p in prompts[:4]]
        outs = [f.result(timeout=900) for f in futs]
        outs.append(eng.submit(prompts[4], max_new_tokens=24)
                    .result(timeout=900))
        status = dbgsrv._collect_status()[eng._status_name]
        assert status["recurrent_state"]["state_impl"] == "pallas"
    for p, o in zip(prompts, outs):
        toks = list(o["output_ids"])
        assert served_gap(params, d, p, toks) <= TOL
        want = np.asarray(net.generate(jnp.asarray([p], jnp.int32), 24))
        assert toks == want[0, len(p):].tolist()


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


def _draft():
    pt.seed(0)
    return GPTForCausalLM(gpt_config("gpt2-small", num_layers=1,
                                     hidden_size=32, num_heads=2,
                                     vocab_size=128))


def test_what_the_state_does_not_compose_with_is_refused_by_name(model):
    net, _, _ = model
    with pytest.raises(RecurrentStateUnsupported) as e:
        LLMEngine(net, max_seqs=2, **ENGINE, draft_net=_draft())
    assert e.value.mechanism == "speculative_verify"
    with LLMEngine(net, max_seqs=2, **ENGINE, prefix_cache=True) as eng:
        assert eng._cache is None
        for call in (lambda: eng.export_pages([]),
                     lambda: eng.import_pages({})):
            with pytest.raises(RecurrentStateUnsupported) as e:
                call()
            assert e.value.mechanism == "kv_page_migration"
        status = dbgsrv._collect_status()[eng._status_name]
        (group,) = status["cache_groups"]
        # (the heads a page STORES: the model's six and two of zeros)
        assert (group["name"], group["layers"], group["kv_heads"],
                group["row_bytes"]) == ("full", 2, 8, 2 * 2 * 8 * 8 * 4)
        state = status["recurrent_state"]
        assert state["rule"] == "delta, scalar decay"
        assert state["stored_shape"] == {"conv_state": [3, 96],
                                         "ssm_state": [3, 8, 128]}
        # (the platform decides: "xla" off the TPU)
        assert state["state_impl"] == "xla" and state["rows"] == 3
        assert state["row_bytes"] == {"conv_state": 6 * 3 * 96 * 4,
                                      "ssm_state": 6 * 3 * 8 * 128 * 4}
        assert status["prefix_cache"]["enabled"] is False


def test_the_issue_phases_say_what_the_state_and_the_group_moved(model):
    """While tracing: ``kv_groups`` names the ``full`` group at the bytes
    its page STORES, ``state_rows`` / ``state_bytes`` the rows the tick
    advanced at the bytes a row stores."""
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
    full = a["kv_groups"]["full"]
    assert full["read"] == full["live"] == a["kv_pages_read"] \
        == -(-a["context_tokens"] // 8)
    assert full["page_bytes"] == page_bytes == 2 * 2 * 8 * 8 * 8 * 4
    assert a["state_rows"] == 1
    # the plain step reads and writes every slot's row
    assert a["state_bytes"] == 2 * 2 * (row["conv_state"]
                                        + row["ssm_state"])


def test_config_refuses_what_the_model_does_not_compute():
    for over in (dict(rope_parameters={"rope_theta": 10000.0}),
                 dict(linear_num_key_heads=1), dict(attention_bias=True),
                 dict(tie_word_embeddings=True), dict(hidden_act="gelu")):
        with pytest.raises(NotImplementedError):
            OlmoHybridConfig(**dict(TINY, **over))
    with pytest.raises(ValueError, match="names a kind"):
        OlmoHybridConfig(**dict(TINY, layer_types=PERIOD))
    with pytest.raises(ValueError, match="names a kind"):
        OlmoHybridConfig(**dict(TINY, layer_types=["mamba"] * 8))


def test_serve_llm_answers_a_post_through_the_engines_programs(model):
    """``POST /generate`` on ``serve_llm`` over the model: the tokens of
    ``generate``, through the mixed and the decode program."""
    import json
    import urllib.request
    from paddle_tpu.inference.llm import serve_llm
    net, _, _ = model
    prompt = prompts_of((37,), seed=9)[0]
    with LLMEngine(net, max_seqs=2, **ENGINE) as eng:
        srv = serve_llm(eng)
        try:
            url = "http://%s:%d/generate" % srv.server_address[:2]
            req = urllib.request.Request(
                url, json.dumps({"prompt_ids": prompt,
                                 "max_new_tokens": 8}).encode(),
                {"Content-Type": "application/json"})
            got = json.load(urllib.request.urlopen(req, timeout=600))
        finally:
            srv.shutdown()
            srv.server_close()
        assert eng.n_mixed_slabs > 0 and eng.n_decode_ticks > 0
    want = np.asarray(net.generate(jnp.asarray([prompt], jnp.int32), 8))
    assert list(got["output_ids"]) == want[0, len(prompt):].tolist()


def test_engine_programs_carry_the_rules_and_the_attentions_scopes(model):
    """The device scopes a trace's ``tf_op`` carries
    (``tools/trace_scopes.py`` prints them a program): the rule's step under
    ``gdn/gdn_step`` in both programs, its chunk form and the shared inverse
    under ``gdn/gdn_chunk`` in the mixed one alone."""
    import re

    class Spy:
        def __init__(self, fn):
            self.fn, self.args = fn, None

        def __call__(self, *args):
            self.args = jax.tree_util.tree_map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype)
                if hasattr(x, "shape") and hasattr(x, "dtype") else x, args)
            return self.fn(*args)

        def text(self):
            return self.fn.lower(*self.args).as_text(debug_info=True)

    net, _, _ = model
    with LLMEngine(net, max_seqs=2, **ENGINE) as eng:
        mixed, decode = Spy(eng._mixed_fn), Spy(eng._decode_fn)
        eng._mixed_fn, eng._decode_fn = mixed, decode
        eng.submit(prompts_of((21,))[0], max_new_tokens=4).result(
            timeout=600)
        texts = {"mixed": mixed.text(), "decode": decode.text()}
    both = ("gdn", "gdn/conv", "gdn/gate", "gdn/gdn_step", "attn_full",
            "attn_full/qk_norm", "attn_full/kv_write", "ln", "mlp",
            "lm_head", "embed")
    chunk = ("gdn/gdn_chunk", "gdn/gdn_chunk/inverse")
    for name, text in texts.items():
        for scope in both + (chunk if name == "mixed" else ()):
            assert re.search(rf"[/(]{scope}[/)]", text), (name, scope)
    assert "gdn_chunk" not in texts["decode"]
