"""Prefix caching + chunked ragged prefill (ISSUE 2 tentpole).

Strategy mirrors test_llm_engine.py: EXACTNESS first (cache on == off,
chunked == one-shot, engine == dense generate — the paged machinery
recomputes identical math over shared memory), then the behaviors only
this subsystem can express: page-granular copy-on-write divergence,
LRU eviction of refcount-zero pages under pressure (and never of live
shared pages), and prefill/decode tick interleaving (a long prompt no
longer stalls in-flight decodes; admission never host-syncs)."""

import time

import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu.inference.llm import LLMEngine
from paddle_tpu.inference.prefix_cache import PrefixCache, page_digests
from paddle_tpu.models.gpt import GPTForCausalLM, gpt_config


def tiny_gpt(max_pos=96):
    pt.seed(0)
    cfg = gpt_config("gpt2-small", num_layers=2, hidden_size=64,
                     num_heads=4, vocab_size=97,
                     max_position_embeddings=max_pos,
                     hidden_dropout=0.0, attention_dropout=0.0)
    return GPTForCausalLM(cfg)


def dense_ref(net, prompt, n):
    return np.asarray(net.generate(jnp.asarray([prompt]),
                                   max_new_tokens=n))[0,
                                                      len(prompt):].tolist()


# -- host-side cache mechanics (no device) ------------------------------


def test_page_digests_roll_and_diverge():
    ps = 4
    a = list(range(10))                   # 2 full pages + tail
    b = a[:6] + [99, 98, 97, 96]          # diverges MID page 1
    da, db = page_digests(a, ps), page_digests(b, ps)
    assert len(da) == 2 and len(db) == 2
    assert da[0] == db[0]                 # identical first page
    assert da[1] != db[1]                 # divergent second page
    # rolling: the digest commits to history, not just its own chunk
    c = [5, 5, 5, 5] + a[4:8]
    assert page_digests(c, ps)[1] != da[1]


def test_prefix_cache_refcounts_lru_and_eviction():
    c = PrefixCache(4)
    d = page_digests(list(range(12)), 4)
    assert c.lookup(d) == []
    assert c.register(d[0], 7) and c.register(d[1], 8)
    assert c.lookup(d) == [7, 8]
    assert c.shared_page_count == 2 and c.evictable_count == 0
    # second sequence maps both; owner releases; pages stay cached
    c.acquire(7), c.acquire(8)
    c.release(7), c.release(8)            # owner done
    assert c.evictable_count == 0         # second holder still live
    c.release(7), c.release(8)
    assert c.evictable_count == 2         # refcount 0: evictable, cached
    assert c.lookup(d) == [7, 8]          # ... and still matchable
    # duplicate digest: second page stays private
    assert not c.register(d[0], 9)
    # LRU: 7 was released first -> evicted first
    assert c.evict_one() == 7
    assert c.lookup(d) == []              # chain broken at page 0
    assert c.flush() == [8]
    assert c.shared_page_count == 0


# -- exactness ----------------------------------------------------------


def run_engine(net, prompts, n_new, temperature=0.0, sequential=True,
               **kw):
    kw.setdefault("max_seqs", 2)
    kw.setdefault("page_size", 4)
    kw.setdefault("num_pages", 128)
    with LLMEngine(net, **kw) as eng:
        if sequential:
            outs = [eng.submit(p, max_new_tokens=n_new,
                               temperature=temperature).result(
                                   timeout=300) for p in prompts]
        else:
            outs = eng.generate(prompts, max_new_tokens=n_new,
                                temperature=temperature)
        stats = (eng.n_cached_tokens, eng.n_prompt_tokens,
                 len(eng._free_pages))
    # close() flushed the cache: every page must be back in the pool
    assert len(eng._free_pages) == eng.num_pages - 1, \
        "pages leaked through the prefix cache"
    return outs, stats


@pytest.mark.parametrize("temperature", [0.0, 0.8],
                         ids=["greedy", "sampled"])
def test_generations_identical_cache_on_vs_off(temperature):
    """The tentpole exactness pin: shared-prefix workload, cache on ==
    cache off, token for token — greedy AND seeded sampling (sampling
    keys derive from request nonce + position, not scheduler state)."""
    net = tiny_gpt()
    rng = np.random.RandomState(0)
    common = rng.randint(0, 97, 16).tolist()
    prompts = [common + rng.randint(0, 97, 3 + i).tolist()
               for i in range(4)]
    on, (cached_on, total_on, _) = run_engine(
        net, prompts, 8, temperature, prefix_cache=True)
    off, (cached_off, _, _) = run_engine(
        net, prompts, 8, temperature, prefix_cache=False)
    assert cached_off == 0
    # sequential submission: requests 2..4 each reuse the 4 full
    # common-prefix pages (16 tokens) the first request registered
    assert cached_on == 3 * 16, cached_on
    for a, b in zip(on, off):
        assert a["output_ids"] == b["output_ids"]
        assert not a["truncated"]
    if temperature == 0.0:
        for a, p in zip(on, prompts):
            assert a["output_ids"] == dense_ref(net, p, 8)


def test_chunked_prefill_matches_one_shot_and_dense():
    """Logit parity across chunkings: a 3-token chunk (page-misaligned
    on purpose: pages fill across chunk boundaries) produces the same
    tokens as a one-shot chunk and as the dense reference."""
    net = tiny_gpt()
    rng = np.random.RandomState(1)
    prompts = [rng.randint(0, 97, n).tolist() for n in (13, 7, 18)]
    want = [dense_ref(net, p, 6) for p in prompts]
    small, _ = run_engine(net, prompts, 6, sequential=False,
                          prefill_chunk=3)
    big, _ = run_engine(net, prompts, 6, sequential=False,
                        prefill_chunk=64)
    for s, b, w in zip(small, big, want):
        assert s["output_ids"] == w
        assert b["output_ids"] == w


def test_copy_on_write_divergence_mid_page():
    """Two prompts share 6 tokens then diverge INSIDE page 1: only the
    fully-identical page 0 is shared; the divergent page is a private
    copy (hash miss -> recompute), and both generations stay exact."""
    net = tiny_gpt()
    rng = np.random.RandomState(2)
    a = rng.randint(0, 97, 9).tolist()
    b = a[:6] + [(t + 1) % 97 for t in a[6:]]
    with LLMEngine(net, max_seqs=2, page_size=4, num_pages=64,
                   prefill_chunk=16) as eng:
        out_a = eng.submit(a, max_new_tokens=6).result(timeout=300)
        hits_after_a = eng.n_cached_tokens
        out_b = eng.submit(b, max_new_tokens=6).result(timeout=300)
        hits_after_b = eng.n_cached_tokens
        # a third request repeating A hits BOTH of A's full pages
        out_a2 = eng.submit(a, max_new_tokens=6).result(timeout=300)
        hits_after_a2 = eng.n_cached_tokens
    assert hits_after_a == 0
    assert hits_after_b - hits_after_a == 4    # page 0 only (4 tokens)
    assert hits_after_a2 - hits_after_b == 8   # pages 0 and 1
    assert out_a["output_ids"] == dense_ref(net, a, 6)
    assert out_b["output_ids"] == dense_ref(net, b, 6)
    assert out_a2["output_ids"] == out_a["output_ids"]


def test_eviction_reclaims_dead_pages_never_live_ones():
    """Page pressure: refcount-zero cached pages are reclaimed (LRU),
    pages mapped by a LIVE sequence never are — the competing request
    truncates instead, and the live request's stream stays exact."""
    net = tiny_gpt(max_pos=64)
    rng = np.random.RandomState(3)
    a = rng.randint(0, 97, 8).tolist()
    big = rng.randint(0, 97, 16).tolist()

    # phase 1: A completes; its 2 full pages stay cached at refcount 0
    with LLMEngine(net, max_seqs=1, page_size=4, num_pages=6,
                   prefill_chunk=16) as eng:
        out_a = eng.submit(a, max_new_tokens=4).result(timeout=300)
        assert out_a["output_ids"] == dense_ref(net, a, 4)
        assert eng._cache.shared_page_count == 2
        assert eng._cache.evictable_count == 2
        # phase 2: BIG needs 4 of 5 usable pages -> evicts A's pages
        out_big = eng.submit(big, max_new_tokens=4).result(timeout=300)
        assert out_big["output_ids"] == dense_ref(net, big, 4)
        assert eng._cache.n_evicted >= 1
        # phase 3: A again — its pages are gone (miss), output exact
        cached0 = eng.n_cached_tokens
        out_a2 = eng.submit(a, max_new_tokens=4).result(timeout=300)
        assert out_a2["output_ids"] == out_a["output_ids"]
        assert eng.n_cached_tokens == cached0   # evicted -> full miss

    # live pages: A decodes while BIG starves the pool — BIG truncates
    # (or finishes short), A's tokens are NEVER corrupted
    net2 = tiny_gpt(max_pos=64)
    with LLMEngine(net2, max_seqs=2, page_size=4, num_pages=6,
                   prefill_chunk=16) as eng:
        fa = eng.submit(a, max_new_tokens=4)
        fb = eng.submit(big, max_new_tokens=8)
        out_a = fa.result(timeout=300)
        out_b = fb.result(timeout=300)
    assert out_a["output_ids"] == dense_ref(net2, a, 4)
    ref_b = dense_ref(net2, big, 8)
    assert out_b["output_ids"] == ref_b[:len(out_b["output_ids"])]


# -- scheduling ---------------------------------------------------------


def test_long_prompt_interleaves_with_decode(issue_phases):
    """The acceptance pin: a prompt longer than one chunk does not
    block in-flight decodes — its chunks ride mixed dispatches that
    also carry the short request's decode row, which RECEIVES tokens
    meanwhile; the tick-ratio metric is populated, and admission
    performed no blocking device fetch (the whole point of the async
    first-token harvest)."""
    from paddle_tpu.observability import metrics as obs
    from paddle_tpu.observability import tracing

    net = tiny_gpt(max_pos=96)
    rng = np.random.RandomState(4)
    short = rng.randint(0, 97, 4).tolist()
    long_p = rng.randint(0, 97, 40).tolist()
    tracing.enable()
    with LLMEngine(net, max_seqs=2, page_size=4, num_pages=128,
                   prefill_chunk=4) as eng:
        fa = eng.submit(short, max_new_tokens=40)
        while not eng.n_decode_ticks:   # the short request in decode
            time.sleep(0.002)
        fb = eng.submit(long_p, max_new_tokens=4)   # 10 prefill chunks
        out_a = fa.result(timeout=300)
        out_b = fb.result(timeout=300)
        hist = "".join(eng.tick_history)
        assert eng.n_prefill_ticks >= 11
        assert eng.n_mixed_slabs >= 11
        assert eng.n_decode_ticks > 0
    tracing.disable()
    assert out_a["output_ids"] == dense_ref(net, short, 40)
    assert out_b["output_ids"] == dense_ref(net, long_p, 4)
    # a token for the short request out of (nearly) every dispatch that
    # carried one of the long prompt's ten chunks
    beside = issue_phases.tokens_beside_chunks(tracing.finished_spans(),
                                               len(long_p))
    assert len(beside) >= 8 and all(beside.values()), (beside, hist)
    snap = obs.default_registry().snapshot()
    assert snap["llm_prefill_ticks"] >= 11
    assert snap["llm_decode_ticks"] > 0
    assert snap["llm_prefill_decode_tick_ratio"] > 0
    assert snap["llm_prefix_cache_hit_rate"] >= 0


def test_submit_validates_total_length_against_max_len():
    """submit() must bound prompt + max_new_tokens by the page-table
    horizon (max_len)."""
    net = tiny_gpt(max_pos=96)
    with LLMEngine(net, max_seqs=1, page_size=4, num_pages=64,
                   max_len=32) as eng:
        with pytest.raises(ValueError, match="max_len"):
            eng.submit(list(range(20)), max_new_tokens=20)
        # fits the horizon exactly -> admitted and completes
        out = eng.submit(list(range(1, 17)),
                         max_new_tokens=16).result(timeout=300)
        assert len(out["output_ids"]) == 16
        assert not out["truncated"]


@pytest.mark.parametrize("beside, budget", [(False, 0), (True, 0),
                                            (True, 1)],
                         ids=["alone", "beside_decode_row",
                              "beside_decode_row_retried"])
def test_prefill_queue_and_inflight_survive_device_error(beside, budget):
    """A device error in the mixed dispatch that carries a prompt's chunk
    (alone, or beside a live request's decode row) resolves every
    request in it as docs/RELIABILITY.md says: failed with the error
    where the retry budget is spent, re-admitted and token-identical
    where it is not; pages reclaimed, no dangling queue entry, and the
    engine keeps serving."""
    net = tiny_gpt()
    eng = LLMEngine(net, max_seqs=2, page_size=4, num_pages=64,
                    prefill_chunk=16, device_retry_budget=budget)
    real = eng._mixed_fn
    struck = []

    def flaky(params, buffers, carry, xs, *rest):
        rows = (bool(np.asarray(carry.budgets).any()),
                bool(np.asarray(xs["lim"]).any()))
        if not struck and rows == (beside, True):
            struck.append(rows)
            raise RuntimeError("transient PJRT failure")
        return real(params, buffers, carry, xs, *rest)

    jobs = [([1, 2, 3, 4, 5, 6], 4)]
    futs = []
    if beside:
        # a live request in decode BEFORE the patched site is armed
        jobs.insert(0, ([9, 8, 7, 6, 5], 40))
        futs.append(eng.submit(jobs[0][0], max_new_tokens=jobs[0][1]))
        while not eng.n_decode_ticks:
            time.sleep(0.002)
    eng._mixed_fn = flaky
    futs.append(eng.submit(jobs[-1][0], max_new_tokens=jobs[-1][1]))
    for f, (prompt, n) in zip(futs, jobs):
        if budget:
            assert f.result(timeout=60)["output_ids"] == \
                dense_ref(net, prompt, n)
        else:
            with pytest.raises(RuntimeError, match="transient"):
                f.result(timeout=60)
    assert struck == [(beside, True)]
    assert not eng._prefill_q          # no dangling queue entry
    ok = eng.submit([7, 8, 9], max_new_tokens=3).result(timeout=60)
    assert ok["output_ids"] == dense_ref(net, [7, 8, 9], 3)
    assert eng.health == "healthy"     # a successful fetch ended the streak
    eng.close()
    assert len(eng._free_pages) == eng.num_pages - 1
