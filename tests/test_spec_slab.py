"""On-device speculative slab (draft-K/verify-1 rounds inside the
DecodeCarry scan) — sampling semantics.

Layer-level (fast tier): the rejection-sampling acceptance rule
``_spec_accept`` reduces EXACTLY to greedy prefix acceptance at T=0,
and at T>0 the first committed token's marginal distribution equals
the target model's one-token-at-a-time sampler ``softmax(logits/T)``
REGARDLESS of draft quality (the speculative-sampling theorem, checked
by Monte-Carlo over the nonce lane — the same lane that varies across
real requests).

Engine-level: greedy slab output is token-identical to a
target-only engine across prefix cache on/off × fused-slab width
N∈{1,8} × kv_dtype, with all four previously-excluded knobs (cache,
N>1 slabs, mixed ticks, int8) enabled SIMULTANEOUSLY on one spec
engine; temperature>0 realized streams are nonce-pinned deterministic
across cache/slab/batch-shape configurations (the failover
token-identity contract)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu.inference.llm import (LLMEngine, _SPEC_DRAFT_SALT,
                                      _spec_accept)
from paddle_tpu.models.gpt import GPTForCausalLM, gpt_config


def _target():
    pt.seed(0)
    cfg = gpt_config("gpt2-small", num_layers=2, hidden_size=64,
                     num_heads=4, vocab_size=97,
                     max_position_embeddings=64, hidden_dropout=0.0,
                     attention_dropout=0.0)
    return GPTForCausalLM(cfg)


def _draft():
    pt.seed(123)
    cfg = gpt_config("gpt2-small", num_layers=1, hidden_size=32,
                     num_heads=2, vocab_size=97,
                     max_position_embeddings=64, hidden_dropout=0.0,
                     attention_dropout=0.0)
    return GPTForCausalLM(cfg)


# ---------------------------------------------------------------- #
# layer level: _spec_accept                                        #
# ---------------------------------------------------------------- #

def test_spec_accept_greedy_reduction():
    """T=0: acceptance is EXACT prefix matching of the proposals
    against the verifier's argmax chain, and the committed run is the
    argmax chain itself — so greedy slab decoding cannot depend on
    the draft distribution (only on how LONG its guesses match)."""
    V, K, B = 11, 4, 3
    rng = np.random.RandomState(0)
    vlg = jnp.asarray(rng.randn(B, K, V), jnp.float32)
    dlg = jnp.asarray(rng.randn(B, K - 1, V), jnp.float32)
    greedy = np.asarray(jnp.argmax(vlg, axis=-1))      # [B, K]
    toks = np.zeros((B, K), np.int32)
    toks[:, 0] = 5
    # slot 0: all proposals right; slot 1: first wrong; slot 2:
    # right, wrong, (ignored)
    toks[0, 1:] = greedy[0, :K - 1]
    toks[1, 1] = (greedy[1, 0] + 1) % V
    toks[1, 2:] = greedy[1, 1:K - 1]
    toks[2, 1] = greedy[2, 0]
    toks[2, 2] = (greedy[2, 1] + 3) % V
    toks[2, 3] = greedy[2, 2]
    out, n_acc = _spec_accept(
        jnp.asarray(toks), dlg, vlg,
        jnp.zeros((B,), jnp.float32),                  # T = 0
        jnp.arange(B, dtype=jnp.int32),
        jnp.full((B,), 9, jnp.int32), jax.random.PRNGKey(3))
    out, n_acc = np.asarray(out), np.asarray(n_acc)
    assert n_acc.tolist() == [K - 1, 0, 1]
    for b in range(B):
        # committed tokens (first n_acc+1) ARE the greedy chain
        assert out[b, :n_acc[b] + 1].tolist() == \
            greedy[b, :n_acc[b] + 1].tolist()


def test_spec_accept_first_token_marginal():
    """T>0 Monte-Carlo over the nonce lane: the first committed
    token's empirical marginal matches the target's sequential
    sampler softmax(vlg/T) even though proposals come from a very
    DIFFERENT draft distribution — accept + residual must conspire
    to exactness (speculative sampling theorem)."""
    V, K, T = 7, 3, 0.7
    rng = np.random.RandomState(0)
    vlg = jnp.asarray(rng.randn(1, K, V) * 2.0, jnp.float32)
    dlg = jnp.asarray(rng.randn(1, K - 1, V) * 2.0, jnp.float32)
    temps = jnp.asarray([T], jnp.float32)
    positions = jnp.asarray([5], jnp.int32)
    key = jax.random.PRNGKey(7)

    @jax.jit
    def one(nonce):
        n = jnp.asarray([nonce], jnp.int32)
        # proposal ~ q via the DRAFT-salted chain, exactly the key
        # the slab's draft probe folds for this (nonce, position)
        dk = jax.random.fold_in(key, _SPEC_DRAFT_SALT)
        kk = jax.random.fold_in(jax.random.fold_in(dk, n[0]),
                                positions[0])
        prop = jax.random.categorical(kk, dlg[0, 0] / T)
        toks = jnp.concatenate(
            [jnp.zeros((1, 1), jnp.int32), prop[None, None],
             jnp.zeros((1, K - 2), jnp.int32)], axis=1)
        out, _ = _spec_accept(toks, dlg, vlg, temps, n, positions,
                              key)
        return out[0, 0]

    trials = 3000
    counts = np.zeros(V)
    for t in range(trials):
        counts[int(one(t))] += 1
    emp = counts / trials
    ref = np.asarray(jax.nn.softmax(vlg[0, 0] / T))
    assert float(np.max(np.abs(emp - ref))) < 0.03, (emp, ref)


# ---------------------------------------------------------------- #
# engine level                                                     #
# ---------------------------------------------------------------- #

@pytest.mark.parametrize("cache", [True, False], ids=["cache", "nocache"])
@pytest.mark.parametrize("n_ticks", [1, 8], ids=["n1", "n8"])
def test_greedy_slab_identity_vs_target_only(cache, n_ticks):
    """Greedy spec slab == target-only engine, with the prefix cache
    and fused slabs ON for the spec engine — the lifted exclusions
    must not move a single token."""
    net, draft = _target(), _draft()
    rng = np.random.RandomState(1)
    prompts = [rng.randint(0, 97, n).tolist() for n in (4, 9, 3)]
    with LLMEngine(net, max_seqs=2, page_size=4, num_pages=64,
                   prefill_chunk=8) as ref:
        want = [o["output_ids"]
                for o in ref.generate(prompts, max_new_tokens=10)]
    with LLMEngine(net, max_seqs=2, page_size=4, num_pages=64,
                   prefill_chunk=8, draft_net=draft,
                   spec_tokens=3, prefix_cache=cache,
                   decode_ticks_per_dispatch=n_ticks) as eng:
        assert eng.spec_k
        free0 = len(eng._free_pages)
        outs = eng.generate(prompts, max_new_tokens=10)
    assert len(eng._free_pages) == eng.num_pages - 1  # close() flushed
    assert free0 <= eng.num_pages - 1
    assert [o["output_ids"] for o in outs] == want


def test_greedy_slab_identity_int8_all_knobs():
    """int8 spec engine (quantized draft pool) + prefix cache + N=8
    fused slabs + mixed ticks, all simultaneously: token-identical to
    the target-only int8 engine (quantization moves logits, so the
    reference is int8 too)."""
    net, draft = _target(), _draft()
    rng = np.random.RandomState(2)
    prompts = [rng.randint(0, 97, n).tolist() for n in (5, 11, 3)]
    with LLMEngine(net, max_seqs=2, page_size=4, num_pages=64,
                   prefill_chunk=8, kv_dtype="int8") as ref:
        want = [o["output_ids"]
                for o in ref.generate(prompts, max_new_tokens=10)]
    with LLMEngine(net, max_seqs=2, page_size=4, num_pages=64,
                   prefill_chunk=8, draft_net=draft,
                   spec_tokens=3, kv_dtype="int8",
                   decode_ticks_per_dispatch=8) as eng:
        assert eng.spec_k and eng._cache is not None
        outs = eng.generate(prompts, max_new_tokens=10)
        assert eng.n_spec_rounds > 0 and eng.n_mixed_slabs > 0
    assert [o["output_ids"] for o in outs] == want


def test_temp_rejection_nonce_pinned_determinism():
    """temperature>0 slab decoding: realized streams depend ONLY on
    (nonce, position) — identical across prefix cache on/off, slab
    width, and batch shape (the cross-replica failover contract)."""
    net, draft = _target(), _draft()
    rng = np.random.RandomState(3)
    prompts = [rng.randint(0, 97, n).tolist() for n in (4, 7, 3)]

    def run(**kw):
        ms = kw.pop("max_seqs", 2)
        with LLMEngine(net, max_seqs=ms, page_size=4, num_pages=64,
                       prefill_chunk=8, draft_net=draft,
                       spec_tokens=3, **kw) as eng:
            futs = [eng.submit(p, max_new_tokens=10, temperature=0.8,
                               nonce=100 + i)
                    for i, p in enumerate(prompts)]
            return [f.result(timeout=300)["output_ids"] for f in futs]

    base = run()
    assert all(len(o) == 10 for o in base)
    assert run(prefix_cache=False) == base
    assert run(decode_ticks_per_dispatch=8) == base
    assert run(max_seqs=1) == base
    # a different nonce moves the stream (the lane is real)
    with LLMEngine(net, max_seqs=2, page_size=4, num_pages=64,
                   prefill_chunk=8, draft_net=draft,
                   spec_tokens=3) as eng:
        other = eng.submit(prompts[0], max_new_tokens=10,
                           temperature=0.8,
                           nonce=999).result(timeout=300)
    assert other["output_ids"] != base[0]
