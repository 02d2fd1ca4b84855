"""Continuous-batching LLM decode engine (VERDICT r4 item 4: the
serving-era analog of the reference's AnalysisPredictor,
reference: paddle/fluid/inference/api/analysis_predictor.h:95).

Strategy: exact greedy parity against GPTForCausalLM.generate (the
paged path recomputes the same math over a different memory layout),
then serving behaviors the dense predictor can't express: token-level
admission, page-pool exhaustion, concurrent HTTP clients."""

import threading

import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu.inference.llm import LLMEngine, serve_llm
from paddle_tpu.models.gpt import GPTForCausalLM, gpt_config, llama_config


def tiny_gpt(**kw):
    pt.seed(0)
    cfg = gpt_config("gpt2-small", num_layers=2, hidden_size=64,
                     num_heads=4, vocab_size=97,
                     max_position_embeddings=96, hidden_dropout=0.0,
                     attention_dropout=0.0, **kw)
    return GPTForCausalLM(cfg)


def tiny_llama():
    pt.seed(0)
    cfg = llama_config(hidden_size=64, num_layers=2, num_heads=4,
                       num_kv_heads=2, vocab_size=97,
                       max_position_embeddings=96, ffn_hidden_size=128)
    return GPTForCausalLM(cfg)


@pytest.mark.parametrize("prefill_chunk", [16, 3], ids=["chunk16", "chunk3"])
@pytest.mark.parametrize("build", [tiny_gpt, tiny_llama],
                         ids=["gpt2", "llama-gqa"])
def test_engine_greedy_matches_dense_generate(build, prefill_chunk):
    """``chunk3``: a chunk smaller than a page (4) and than two of the
    three prompts, so a prompt crosses several mixed ticks and its chunk
    boundaries lie off the page grid."""
    net = build()
    rng = np.random.RandomState(0)
    prompts = [rng.randint(0, 97, n).tolist() for n in (5, 11, 3)]
    want = [np.asarray(net.generate(jnp.asarray([p]), max_new_tokens=8)
                       )[0, len(p):].tolist() for p in prompts]
    with LLMEngine(net, max_seqs=4, page_size=4, num_pages=128,
                   prefill_chunk=prefill_chunk) as eng:
        outs = eng.generate(prompts, max_new_tokens=8)
        # 5 + 11 + 3 prompt tokens, packed: ceil(19 / chunk) chunks at least
        assert eng.n_prefill_ticks >= -(-19 // prefill_chunk)
    for got, ref, p in zip(outs, want, prompts):
        assert got["output_ids"] == ref, (p, got["output_ids"], ref)
        assert not got["truncated"]
        assert got["ttft_s"] is not None and got["latency_s"] > 0


def gpt2_heads():
    """``gpt2-small``'s published twelve heads (48 = 12 x 4)."""
    pt.seed(0)
    return GPTForCausalLM(gpt_config(
        "gpt2-small", num_layers=2, hidden_size=48, vocab_size=97,
        max_position_embeddings=96, hidden_dropout=0.0,
        attention_dropout=0.0))


def llama_six_kv_heads():
    pt.seed(0)
    return GPTForCausalLM(llama_config(
        hidden_size=48, num_layers=2, num_heads=12, num_kv_heads=6,
        vocab_size=97, max_position_embeddings=96, ffn_hidden_size=96))


@pytest.mark.parametrize("build,kv_heads", [(gpt2_heads, 12),
                                            (llama_six_kv_heads, 6)],
                         ids=["gpt2-12-heads", "gqa-6-kv-heads"])
def test_engine_serves_head_counts_that_are_no_sublane_tile(build, kv_heads):
    """A model reads its K/V head count off the pool's shape
    (``ragged_paged_attention``): the pool stores the heads the model
    names, 12 as 12 and 6 as 6, and the gathered path serves them."""
    net = build()
    assert net.cfg.num_heads == 12
    rng = np.random.RandomState(2)
    prompts = [rng.randint(0, 97, n).tolist() for n in (9, 4)]
    want = [np.asarray(net.generate(jnp.asarray([p]), max_new_tokens=6)
                       )[0, len(p):].tolist() for p in prompts]
    with LLMEngine(net, max_seqs=2, page_size=4, num_pages=64,
                   prefill_chunk=8, attention_impl="xla") as eng:
        assert eng.k_pages[0].shape[-2:] == (kv_heads, 4)
        outs = eng.generate(prompts, max_new_tokens=6)
    assert [o["output_ids"] for o in outs] == want


def test_engine_continuous_admission_and_page_reuse():
    """Requests joining mid-flight don't perturb running sequences,
    and every page returns to the pool."""
    net = tiny_gpt()
    rng = np.random.RandomState(1)
    p0 = rng.randint(0, 97, 6).tolist()
    p1 = rng.randint(0, 97, 4).tolist()
    ref0 = np.asarray(net.generate(jnp.asarray([p0]),
                                   max_new_tokens=12))[0, len(p0):]
    ref1 = np.asarray(net.generate(jnp.asarray([p1]),
                                   max_new_tokens=6))[0, len(p1):]
    eng = LLMEngine(net, max_seqs=2, page_size=4, num_pages=64,
                    prefill_chunk=8)
    free0 = len(eng._free_pages)
    f0 = eng.submit(p0, max_new_tokens=12)
    # second request lands while the first decodes (token-level join)
    f1 = eng.submit(p1, max_new_tokens=6)
    assert f0.result(timeout=300)["output_ids"] == ref0.tolist()
    assert f1.result(timeout=300)["output_ids"] == ref1.tolist()
    eng.close()
    assert len(eng._free_pages) == free0  # no page leaked
    assert eng.n_steps > 0 and eng.n_tokens >= 18


def test_engine_more_requests_than_slots():
    """8 requests through 2 slots: admission queues and drains."""
    net = tiny_gpt()
    rng = np.random.RandomState(2)
    prompts = [rng.randint(0, 97, 1 + (i % 5)).tolist()
               for i in range(8)]
    with LLMEngine(net, max_seqs=2, page_size=4, num_pages=64,
                   prefill_chunk=8) as eng:
        outs = eng.generate(prompts, max_new_tokens=4)
    assert all(len(o["output_ids"]) == 4 for o in outs)


def test_engine_pool_exhaustion_truncates_gracefully():
    """A pool too small for the request's full length finishes the
    request early with truncated=True instead of crashing the engine
    (the reference predictor's analog failure is a hard OOM)."""
    net = tiny_gpt()
    # 3 usable pages of 4 tokens = 12 cached tokens max
    with LLMEngine(net, max_seqs=1, page_size=4, num_pages=4,
                   prefill_chunk=8) as eng:
        out = eng.generate([[1, 2, 3, 4, 5]], max_new_tokens=40)[0]
    assert out["truncated"]
    assert 0 < len(out["output_ids"]) < 40
    # pool drained and engine still serviceable was exercised by close()


def test_engine_sampling_temperature_and_eos():
    net = tiny_gpt()
    with LLMEngine(net, max_seqs=2, page_size=4, num_pages=64,
                   prefill_chunk=8, eos_token_id=7) as eng:
        out = eng.generate([[3, 1, 4]], max_new_tokens=64,
                           temperature=1.0)[0]
        assert len(out["output_ids"]) >= 1
        # eos stops early when sampled; otherwise runs to length
        if 7 in out["output_ids"]:
            assert out["output_ids"][-1] == 7


def test_http_serving_concurrent_clients():
    """N concurrent clients against one engine through the HTTP front
    (VERDICT done-criterion: N clients decoding from one predictor)."""
    import json
    from urllib.request import Request, urlopen

    net = tiny_gpt()
    rng = np.random.RandomState(3)
    prompts = [rng.randint(0, 97, 2 + i).tolist() for i in range(6)]
    refs = [np.asarray(net.generate(jnp.asarray([p]), max_new_tokens=5)
                       )[0, len(p):].tolist() for p in prompts]
    with LLMEngine(net, max_seqs=4, page_size=4, num_pages=128,
                   prefill_chunk=16) as eng:
        srv = serve_llm(eng)
        host, port = srv.server_address
        results = {}

        def client(i):
            body = json.dumps({"prompt_ids": prompts[i],
                               "max_new_tokens": 5}).encode()
            req = Request(f"http://{host}:{port}/generate", data=body,
                          headers={"Content-Type": "application/json"})
            with urlopen(req, timeout=300) as r:
                results[i] = json.loads(r.read())

        ts = [threading.Thread(target=client, args=(i,))
              for i in range(len(prompts))]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=300)
        srv.shutdown()
    assert len(results) == len(prompts)
    for i, ref in enumerate(refs):
        assert results[i]["output_ids"] == ref


def test_engine_rejects_impossible_requests_cleanly():
    """Failure paths resolve, never hang: a prompt that can NEVER fit
    the page pool fails its future (the chunked path accepts ANY
    prompt length up to max_len, whatever the chunk); a
    device-side error mid-serving fails
    in-flight requests but leaves the engine serving."""
    net = tiny_gpt()
    with LLMEngine(net, max_seqs=1, page_size=4, num_pages=4,
                   prefill_chunk=16) as eng:
        # 20 tokens exceed the largest bucket, which bounds nothing,
        # but need 5 pages where only 3 exist -> future fails
        fut = eng.submit(list(range(20)), max_new_tokens=2)
        with pytest.raises(ValueError, match="cannot fit"):
            fut.result(timeout=60)
        with pytest.raises(ValueError, match="empty prompt"):
            eng.submit([], max_new_tokens=2)
        # 13 tokens need 4 pages; only 3 usable exist -> future fails
        fut = eng.submit([1] * 13, max_new_tokens=2)
        with pytest.raises(ValueError, match="cannot fit"):
            fut.result(timeout=60)

    net2 = tiny_gpt()
    eng = LLMEngine(net2, max_seqs=2, page_size=4, num_pages=64,
                    prefill_chunk=8)
    real_decode = eng._decode_fn
    calls = {"n": 0}

    def flaky(*a, **kw):
        calls["n"] += 1
        if calls["n"] == 1:
            raise RuntimeError("transient PJRT failure")
        return real_decode(*a, **kw)

    eng._decode_fn = flaky
    bad = eng.submit([1, 2, 3], max_new_tokens=4)
    with pytest.raises(RuntimeError, match="transient"):
        bad.result(timeout=60)
    # engine survived: the next request completes
    ok = eng.submit([4, 5], max_new_tokens=3).result(timeout=60)
    assert len(ok["output_ids"]) == 3
    eng.close()


@pytest.mark.parametrize("name", ["mixed_tick", "lookahead",
                                  "prefill_buckets"])
def test_removed_engine_options(name):
    """A queued prompt reaches the device one way: the options that chose
    another (or sized it) are gone, not ignored."""
    from paddle_tpu.core import flags
    value = {"mixed_tick": True, "lookahead": 0,
             "prefill_buckets": (16,)}[name]
    with pytest.raises(TypeError, match=name):
        LLMEngine(tiny_gpt(), **{name: value})
    with pytest.raises(flags.FlagError):
        flags.get_flag("mixed_tick")


@pytest.mark.parametrize("max_len, want", [(None, 64), (40, 40)],
                         ids=["default", "capped_by_max_len"])
def test_prefill_chunk_default(max_len, want):
    """``prefill_chunk`` left unset: 64 tokens, or ``max_len`` where that
    is smaller; the mixed program's chunk rows have that width."""
    with LLMEngine(tiny_gpt(), max_seqs=2, page_size=4, num_pages=64,
                   max_len=max_len) as eng:
        assert eng.prefill_chunk == want
        out = eng.generate([[3, 1, 4, 1, 5]], max_new_tokens=3)[0]
        assert len(out["output_ids"]) == 3
