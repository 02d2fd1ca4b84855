"""Paged attention vs dense reference (serving decode step).

Analog territory: the reference's fused_multi_transformer decode tests;
paged layout per PAPERS.md ragged-paged-attention."""

import math

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from paddle_tpu.ops.paged_attention import PagedKVCache, paged_attention

pytestmark = pytest.mark.slow  # smoke tier skips (tools/ci.sh --smoke)


def _dense_ref(q, k, v, lens):
    b, h, d = q.shape
    outs = []
    for i in range(b):
        ki, vi = k[i, :lens[i]], v[i, :lens[i]]          # [L, h, d]
        lg = np.einsum("hd,lhd->hl", q[i], ki) / math.sqrt(d)
        p = np.exp(lg - lg.max(-1, keepdims=True))
        p = p / p.sum(-1, keepdims=True)
        outs.append(np.einsum("hl,lhd->hd", p, vi))
    return np.stack(outs)


def _build_cache(lens, page_size, kv_heads, d, seed=0):
    r = np.random.RandomState(seed)
    b = len(lens)
    pages_per_seq = -(-max(lens) // page_size)
    cache = PagedKVCache(num_pages=b * pages_per_seq + 2,
                         page_size=page_size, kv_heads=kv_heads,
                         head_dim=d, max_seqs=b,
                         pages_per_seq=pages_per_seq)
    dense_k = np.zeros((b, max(lens), kv_heads, d), np.float32)
    dense_v = np.zeros_like(dense_k)
    for i, L in enumerate(lens):
        kk = r.randn(L, kv_heads, d).astype(np.float32)
        vv = r.randn(L, kv_heads, d).astype(np.float32)
        cache.append(i, jnp.asarray(kk), jnp.asarray(vv))
        dense_k[i, :L], dense_v[i, :L] = kk, vv
    return cache, dense_k, dense_v


def test_matches_dense_ragged_lengths():
    lens = [7, 13, 3]
    kv_heads, d = 2, 8
    cache, dk, dv = _build_cache(lens, page_size=4, kv_heads=kv_heads,
                                 d=d)
    q = np.random.RandomState(1).randn(3, 2, 8).astype(np.float32)
    out = np.asarray(paged_attention(
        jnp.asarray(q), cache.k_pages, cache.v_pages,
        cache.block_tables, cache.context_lens))
    ref = _dense_ref(q, dk, dv, lens)
    np.testing.assert_allclose(out, ref, rtol=1e-4, atol=1e-5)


def test_gqa_heads():
    lens = [5, 9]
    cache, dk, dv = _build_cache(lens, page_size=4, kv_heads=2, d=8,
                                 seed=2)
    q = np.random.RandomState(3).randn(2, 4, 8).astype(np.float32)  # 4 q heads / 2 kv
    out = np.asarray(paged_attention(
        jnp.asarray(q), cache.k_pages, cache.v_pages,
        cache.block_tables, cache.context_lens))
    ref = _dense_ref(q, np.repeat(dk, 2, axis=2),
                     np.repeat(dv, 2, axis=2), lens)
    np.testing.assert_allclose(out, ref, rtol=1e-4, atol=1e-5)


def test_append_and_free_reuse_pages():
    cache, _, _ = _build_cache([4, 4], page_size=4, kv_heads=1, d=4)
    free_before = len(cache._free)
    cache.free(0)
    assert len(cache._free) == free_before + 1
    assert int(cache.context_lens[0]) == 0
    # page gets reused by a new sequence
    cache.append(0, jnp.ones((4, 1, 4)), jnp.ones((4, 1, 4)))
    assert len(cache._free) == free_before


def test_pool_exhaustion_raises():
    cache = PagedKVCache(num_pages=1, page_size=4, kv_heads=1,
                         head_dim=4, max_seqs=2, pages_per_seq=2)
    cache.append(0, jnp.ones((4, 1, 4)), jnp.ones((4, 1, 4)))
    with pytest.raises(RuntimeError, match="exhausted"):
        cache.append(1, jnp.ones((1, 1, 4)), jnp.ones((1, 1, 4)))


def test_jit_compatible_decode_step():
    lens = [6, 2]
    cache, dk, dv = _build_cache(lens, page_size=4, kv_heads=2, d=8,
                                 seed=4)
    q = jnp.asarray(np.random.RandomState(5).randn(2, 2, 8),
                    jnp.float32)
    fn = jax.jit(paged_attention)
    out = np.asarray(fn(q, cache.k_pages, cache.v_pages,
                        cache.block_tables, cache.context_lens))
    ref = _dense_ref(np.asarray(q), dk, dv, lens)
    np.testing.assert_allclose(out, ref, rtol=1e-4, atol=1e-5)


def test_empty_slot_returns_zeros_not_nan():
    cache, dk, dv = _build_cache([4], page_size=4, kv_heads=1, d=4,
                                 seed=6)
    # max_seqs=1 here; build a 2-slot case manually
    cache2 = PagedKVCache(num_pages=4, page_size=4, kv_heads=1,
                          head_dim=4, max_seqs=2, pages_per_seq=1)
    cache2.append(0, jnp.ones((4, 1, 4)), jnp.ones((4, 1, 4)))
    q = jnp.ones((2, 1, 4))
    out = np.asarray(paged_attention(
        q, cache2.k_pages, cache2.v_pages, cache2.block_tables,
        cache2.context_lens))
    assert np.all(np.isfinite(out))
    np.testing.assert_allclose(out[1], 0.0)


def test_capacity_validation():
    cache = PagedKVCache(num_pages=8, page_size=4, kv_heads=1,
                         head_dim=4, max_seqs=1, pages_per_seq=2)
    with pytest.raises(ValueError, match="pages_per_seq"):
        cache.append(0, jnp.ones((12, 1, 4)), jnp.ones((12, 1, 4)))


def test_append_spanning_pages_matches_dense():
    lens = [10]  # spans 3 pages of 4 with a partial page
    cache, dk, dv = _build_cache(lens, page_size=4, kv_heads=2, d=8,
                                 seed=7)
    q = np.random.RandomState(8).randn(1, 2, 8).astype(np.float32)
    out = np.asarray(paged_attention(
        jnp.asarray(q), cache.k_pages, cache.v_pages,
        cache.block_tables, cache.context_lens))
    ref = _dense_ref(q, dk, dv, lens)
    np.testing.assert_allclose(out, ref, rtol=1e-4, atol=1e-5)


def test_kernel_matches_xla_path():
    """The fused Pallas decode kernel (scalar-prefetched block tables,
    per-page streaming) equals the gather+dense XLA path across GQA/
    MHA, partial last pages, empty slots, and bf16 pages."""
    from paddle_tpu.ops.paged_attention import (paged_attention,
                                                paged_attention_kernel)
    rng = np.random.RandomState(1)
    for (H, KVH, PS, dtype) in [(4, 2, 8, jnp.float32),
                                (4, 4, 16, jnp.float32),
                                (8, 2, 8, jnp.bfloat16)]:
        B, D, NP, P = 3, 16, 20, 4
        q = jnp.asarray(rng.randn(B, H, D), jnp.float32)
        kp = jnp.asarray(rng.randn(NP, PS, KVH, D), dtype)
        vp = jnp.asarray(rng.randn(NP, PS, KVH, D), dtype)
        tables = jnp.asarray(
            [[1, 2, 3, 0], [4, 5, 0, 0], [0, 0, 0, 0]], jnp.int32)
        lens = jnp.asarray([2 * PS + 3, PS + 1, 0], jnp.int32)
        ref = np.asarray(paged_attention(q, kp, vp, tables, lens))
        got = np.asarray(paged_attention_kernel(
            q, kp, vp, tables, lens, interpret=True))
        tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
        np.testing.assert_allclose(got, ref, atol=tol, rtol=tol,
                                   err_msg=f"H{H} KVH{KVH} PS{PS}")
        np.testing.assert_allclose(got[2], 0.0)  # empty slot zeros


def test_paged_attention_ragged_matches_chunk_and_kernel():
    """The ragged prefill op: flattening the rectangular [B, K] chunk
    case into T=B*K tokens with per-token tables/limits must reproduce
    paged_attention_chunk exactly, on both the xla and pallas impls."""
    from paddle_tpu.ops.paged_attention import (paged_attention_chunk,
                                                paged_attention_ragged)

    rng = np.random.RandomState(0)
    B, K, H, KVH, PS, D, NP, P = 2, 3, 4, 2, 4, 16, 12, 3
    q = jnp.asarray(rng.randn(B, K, H, D), jnp.float32)
    kp = jnp.asarray(rng.randn(NP, PS, KVH, D), jnp.float32)
    vp = jnp.asarray(rng.randn(NP, PS, KVH, D), jnp.float32)
    tables = jnp.asarray([[1, 2, 3], [4, 5, 0]], jnp.int32)
    base = jnp.asarray([5, 2], jnp.int32)

    ref = np.asarray(paged_attention_chunk(q, kp, vp, tables, base))
    qf = q.reshape(B * K, H, D)
    tf = jnp.repeat(tables, K, axis=0)
    lims = (base[:, None] + jnp.arange(K)[None, :] + 1).reshape(-1)
    got = np.asarray(paged_attention_ragged(qf, kp, vp, tf, lims))
    np.testing.assert_allclose(got, ref.reshape(B * K, H, D),
                               atol=1e-6, rtol=1e-6)
    got_k = np.asarray(paged_attention_ragged(qf, kp, vp, tf, lims,
                                              impl="pallas"))
    np.testing.assert_allclose(got_k, ref.reshape(B * K, H, D),
                               atol=2e-5, rtol=2e-5)
    # padding tokens (limit 0) produce zero rows
    zero = np.asarray(paged_attention_ragged(
        qf, kp, vp, tf, jnp.zeros((B * K,), jnp.int32)))
    np.testing.assert_allclose(zero, 0.0)


def test_engine_with_pallas_attention_matches_dense():
    """LLMEngine(attention_impl='pallas'): greedy decode through the
    fused kernel is token-identical to the dense generate."""
    import paddle_tpu as pt
    from paddle_tpu.inference.llm import LLMEngine
    from paddle_tpu.models.gpt import GPTForCausalLM, gpt_config

    pt.seed(0)
    cfg = gpt_config("gpt2-small", num_layers=2, hidden_size=64,
                     num_heads=4, vocab_size=97,
                     max_position_embeddings=64, hidden_dropout=0.0,
                     attention_dropout=0.0)
    net = GPTForCausalLM(cfg)
    rng = np.random.RandomState(0)
    prompts = [rng.randint(0, 97, n).tolist() for n in (5, 9)]
    want = [np.asarray(net.generate(jnp.asarray([p]), max_new_tokens=6)
                       )[0, len(p):].tolist() for p in prompts]
    with LLMEngine(net, max_seqs=2, page_size=4, num_pages=64,
                   prefill_chunk=16,
                   attention_impl="pallas") as eng:
        outs = eng.generate(prompts, max_new_tokens=6)
    for got, ref in zip(outs, want):
        assert got["output_ids"] == ref
