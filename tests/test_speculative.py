"""Speculative decoding over the paged engine (no reference analog —
the 2026 serving lever; greedy acceptance is EXACT by construction).

Layer-level: one _PagedVerify pass must equal K sequential
_PagedDecode steps — same greedy tokens AND same page contents.
Engine-level (added with the engine wiring): speculative greedy ==
dense generate, with fewer target passes than tokens."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu.inference.llm import (_DraftChunk, _PagedDecode,
                                      _PagedVerify)
from paddle_tpu.models.gpt import GPTForCausalLM, gpt_config, llama_config
from paddle_tpu.nn.layer import functional_call, split_state

PS, NP, P = 4, 32, 8  # page size, pool pages, pages/seq


def _build(gqa: bool):
    pt.seed(0)
    if gqa:
        cfg = llama_config(hidden_size=64, num_layers=2, num_heads=4,
                           num_kv_heads=2, vocab_size=97,
                           max_position_embeddings=64,
                           ffn_hidden_size=128)
    else:
        cfg = gpt_config("gpt2-small", num_layers=2, hidden_size=64,
                         num_heads=4, vocab_size=97,
                         max_position_embeddings=64,
                         hidden_dropout=0.0, attention_dropout=0.0)
    return GPTForCausalLM(cfg)


def _seed_pages(net, prompt):
    """Prefill one slot's pages; returns (pages, tables, ctx, t0)."""
    cfg = net.cfg
    L = cfg.num_layers
    kp = jnp.zeros((L, NP, PS, cfg.num_kv_heads, cfg.head_dim))
    vp = jnp.zeros_like(kp)
    tables = np.zeros((1, P), np.int32)
    for i in range(P):
        tables[0, i] = i + 1
    prefill = _DraftChunk(net)
    params, buffers = split_state(prefill)
    n, T = len(prompt), 16
    ids = np.zeros((T,), np.int32)
    ids[:n] = prompt
    pos = np.arange(T, dtype=np.int32)
    valid = pos < n
    (kp, vp), _ = functional_call(
        prefill, params, buffers, jnp.asarray(ids),
        jnp.asarray(np.where(valid, pos, 0)),
        jnp.asarray(np.where(valid, pos + 1, 0)),
        jnp.asarray(np.repeat(tables, T, axis=0)), kp, vp,
        training=False)
    # the first token after the prompt: the dense forward's
    t0 = np.asarray(net.generate(jnp.asarray([prompt]),
                                 max_new_tokens=1))[0, n]
    return kp, vp, jnp.asarray(tables), n, int(t0)


@pytest.mark.parametrize("gqa", [False, True], ids=["mha", "gqa"])
def test_verify_pass_equals_sequential_decode(gqa):
    net = _build(gqa)
    prompt = [3, 1, 4, 1, 5]
    K = 4

    # (a) K sequential greedy decode steps
    kp, vp, tables, ctx, t0 = _seed_pages(net, prompt)
    decode = _PagedDecode(net)
    params, buffers = split_state(decode)
    toks = [t0]
    for j in range(K):
        (nxt, kp, vp), _ = functional_call(
            decode, params, buffers,
            jnp.asarray([toks[-1]], jnp.int32),
            jnp.asarray([ctx + j], jnp.int32), tables,
            jnp.asarray([ctx + j + 1], jnp.int32), kp, vp,
            jnp.asarray([0.0], jnp.float32),
            jnp.asarray([0], jnp.int32), jax.random.PRNGKey(9),
            training=False)
        toks.append(int(nxt[0]))
    seq_pages = (np.asarray(kp), np.asarray(vp))

    # (b) one verify pass over [t0, d1..d_{K-1}]
    kp2, vp2, tables2, ctx2, t02 = _seed_pages(net, prompt)
    assert t02 == t0
    verify = _PagedVerify(net)
    vparams, vbuffers = split_state(verify)
    (logits, kp2, vp2), _ = functional_call(
        verify, vparams, vbuffers,
        jnp.asarray([toks[:K]], jnp.int32),
        jnp.asarray([ctx2], jnp.int32), tables2, kp2, vp2,
        training=False)
    greedy = jnp.argmax(logits, axis=-1)
    # target greedy after each prefix == the sequential outputs
    assert np.asarray(greedy)[0].tolist() == toks[1:K + 1]
    # page contents identical everywhere the sequential run wrote
    np.testing.assert_allclose(np.asarray(kp2), seq_pages[0],
                               atol=1e-6, rtol=1e-6)
    np.testing.assert_allclose(np.asarray(vp2), seq_pages[1],
                               atol=1e-6, rtol=1e-6)


def test_verify_rejection_prefix_semantics():
    """With WRONG draft tokens, the verify outputs are still the true
    target choices for every prefix up to and including the first
    mismatch — all the acceptance rule reads."""
    net = _build(False)
    prompt = [7, 2, 9]
    kp, vp, tables, ctx, t0 = _seed_pages(net, prompt)
    decode = _PagedDecode(net)
    params, buffers = split_state(decode)
    # true continuation
    (g1, kp_t, vp_t), _ = functional_call(
        decode, params, buffers, jnp.asarray([t0], jnp.int32),
        jnp.asarray([ctx], jnp.int32), tables,
        jnp.asarray([ctx + 1], jnp.int32), kp, vp,
        jnp.asarray([0.0], jnp.float32),
        jnp.asarray([0], jnp.int32), jax.random.PRNGKey(0),
        training=False)
    wrong = (int(g1[0]) + 1) % 97
    kp2, vp2, tables2, ctx2, _ = _seed_pages(net, prompt)
    verify = _PagedVerify(net)
    vparams, vbuffers = split_state(verify)
    (logits, _, _), _ = functional_call(
        verify, vparams, vbuffers,
        jnp.asarray([[t0, wrong, wrong]], jnp.int32),
        jnp.asarray([ctx2], jnp.int32), tables2, kp2, vp2,
        training=False)
    greedy = jnp.argmax(logits, axis=-1)
    # g_0 (after t0) must equal the true next token even though the
    # LATER positions in the chunk carried garbage drafts
    assert int(np.asarray(greedy)[0, 0]) == int(g1[0])


def test_speculative_engine_exact_with_perfect_draft():
    """draft == target: every proposal accepted — outputs EXACTLY match
    dense generate while target passes collapse to ~tokens/K."""
    from paddle_tpu.inference.llm import LLMEngine
    net = _build(False)
    rng = np.random.RandomState(0)
    prompts = [rng.randint(0, 97, n).tolist() for n in (5, 9)]
    want = [np.asarray(net.generate(jnp.asarray([p]),
                                    max_new_tokens=12))[0, len(p):]
            .tolist() for p in prompts]
    with pytest.raises(ValueError, match="spec_tokens"):
        LLMEngine(net, draft_net=net, spec_tokens=1)
    with LLMEngine(net, max_seqs=2, page_size=4, num_pages=64,
                   prefill_chunk=16, draft_net=net,
                   spec_tokens=4) as eng:
        outs = eng.generate(prompts, max_new_tokens=12)
        rounds, toks = eng.n_spec_rounds, eng.n_tokens
    for got, ref in zip(outs, want):
        assert got["output_ids"] == ref
    # every round commits K tokens when the draft is perfect
    assert rounds <= -(-12 // 4) * 2 + 2, (rounds, toks)


def test_speculative_engine_exact_with_imperfect_draft():
    """A DIFFERENT (smaller, differently-initialized) draft: outputs
    still exactly match dense generate — acceptance only changes how
    many target passes it took."""
    from paddle_tpu.inference.llm import LLMEngine
    net = _build(False)
    pt.seed(123)
    dcfg = gpt_config("gpt2-small", num_layers=1, hidden_size=32,
                      num_heads=2, vocab_size=97,
                      max_position_embeddings=64, hidden_dropout=0.0,
                      attention_dropout=0.0)
    draft = GPTForCausalLM(dcfg)
    rng = np.random.RandomState(1)
    prompts = [rng.randint(0, 97, n).tolist() for n in (4, 7, 3)]
    want = [np.asarray(net.generate(jnp.asarray([p]),
                                    max_new_tokens=10))[0, len(p):]
            .tolist() for p in prompts]
    with LLMEngine(net, max_seqs=2, page_size=4, num_pages=64,
                   prefill_chunk=8, draft_net=draft,
                   spec_tokens=3) as eng:
        free0 = len(eng._free_pages)
        outs = eng.generate(prompts, max_new_tokens=10)
    assert len(eng._free_pages) == free0          # no page leaked
    for got, ref in zip(outs, want):
        assert got["output_ids"] == ref
        assert len(got["output_ids"]) == 10


def test_speculative_engine_eos_and_guards():
    from paddle_tpu.inference.llm import LLMEngine
    net = _build(False)
    # chunked ragged prefill takes a prompt longer than any bucket,
    # rejection sampling serves temp>0
    with LLMEngine(net, max_seqs=1, page_size=4, num_pages=64,
                   prefill_chunk=8, draft_net=net,
                   spec_tokens=3, eos_token_id=7) as eng:
        out = eng.generate([list(range(20))], max_new_tokens=4,
                           temperature=0.9)[0]
        assert len(out["output_ids"]) <= 4
        out = eng.generate([[3, 1, 4]], max_new_tokens=40)[0]
        if 7 in out["output_ids"]:
            assert out["output_ids"][-1] == 7
        assert len(out["output_ids"]) <= 40
    with pytest.raises(ValueError, match="spec_tokens"):
        LLMEngine(net, draft_net=net, spec_tokens=1)


def test_speculative_tight_max_len_parity():
    """A request whose tail round cannot fit K positions (engine
    max_len reached) still completes EXACTLY like plain decode —
    acceptance clamps to the cache capacity instead of truncating
    (r5 review finding)."""
    from paddle_tpu.inference.llm import LLMEngine
    net = _build(False)
    rng = np.random.RandomState(5)
    prompt = rng.randint(0, 97, 13).tolist()
    want = np.asarray(net.generate(jnp.asarray([prompt]),
                                   max_new_tokens=3))[0, 13:].tolist()
    with LLMEngine(net, max_seqs=1, page_size=4, num_pages=64,
                   prefill_chunk=16, max_len=16, draft_net=net,
                   spec_tokens=4) as eng:
        out = eng.generate([prompt], max_new_tokens=3)[0]
    assert out["output_ids"] == want
    assert not out["truncated"]


def test_engine_names_no_model_internals():
    """The engine owns the cache and calls ``ragged_forward``; the walk
    over a model's layers lives in the model (so a new model can be a
    target or a draft with no edit here). And the two constructor
    arguments that went with the inline round stay gone."""
    import inspect
    from paddle_tpu.inference import llm
    src = inspect.getsource(llm)
    for name in (".gpt.", "qkv_proj", "ln_1", "ln_2", "out_proj",
                 "_lm_logits", "init_caches", "rope_tables"):
        assert name not in src, name
    params = inspect.signature(llm.LLMEngine).parameters
    assert "spec_slab" not in params and "cache_dtype" not in params
