"""One ragged kernel for mixed prefill+decode, on an int8-quantized
KV pool (ISSUE 15).

Contracts under test:

- ``ragged_paged_attention`` is THE entry point: the decode, chunk and
  ragged wrappers are exact aliases of it (xla AND pallas impls), and
  it serves a mixed batch of prefill rows and decode rows in one call.
- int8 KV (``QuantizedKV``: quantize-on-write, per-token scales,
  dequantize-in-kernel) stays within the documented tolerance of the
  f32-accumulate reference path at the op level, and quantization is
  DETERMINISTIC — cache on/off, fused slabs and the mixed tick all
  produce identical int8 streams.
- the mixed tick serves prompt chunks and decode rows in one fused
  dispatch whose streams do not depend on which rows share a tick:
  TOKEN-IDENTICAL to one slot serving the same prompts in turn and,
  greedy, to ``net.generate`` (greedy AND seeded, cache on/off,
  N in {1, 8}), with a prompt admitted mid-slab decoding on device
  (zero host dispatches between its phases).
- ~2x page capacity at fixed HBM: int8 page bytes (scale table
  included) buy >= 1.8x the pages of bf16, and the memory ledger's
  kv_pool rows split dtype bytes from scale-table bytes while still
  tiling the pool exactly.
"""

import time

import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu.inference.llm import LLMEngine
from paddle_tpu.models.gpt import GPTForCausalLM, gpt_config
from paddle_tpu.ops.paged_attention import (
    QuantizedKV, kv_layer, kv_write, kv_zeros, paged_attention,
    paged_attention_chunk, paged_attention_ragged,
    ragged_paged_attention, ragged_paged_attention_reference)

# the documented int8 quantization tolerances (PERF.md "Ragged mixed
# tick + int8 KV"): op-level attention output within ATOL of the f32
# reference on unit-variance KV; engine-level greedy token agreement
# vs an f32-pool engine at least AGREE on the pinned workload
INT8_ATOL = 0.05
INT8_GREEDY_AGREE = 0.9


def tiny_gpt():
    pt.seed(0)
    cfg = gpt_config("gpt2-small", num_layers=2, hidden_size=64,
                     num_heads=4, vocab_size=97,
                     max_position_embeddings=96, hidden_dropout=0.0,
                     attention_dropout=0.0)
    return GPTForCausalLM(cfg)


def dense_ref(net, prompt, n):
    """``net.generate``'s ``n`` tokens after ``prompt``: the dense forward,
    no engine."""
    return np.asarray(net.generate(jnp.asarray([prompt]),
                                   max_new_tokens=n))[0, len(prompt):].tolist()


# ---------------------------------------------------------------------------
# op level: one entry point, int8 tolerance
# ---------------------------------------------------------------------------


def _filled_stores(rng, L=1, NP=12, PS=4, KVH=2, D=16, pages=(1, 2, 3)):
    q8 = kv_zeros((L, NP, PS, KVH, D), "int8")
    f32 = kv_zeros((L, NP, PS, KVH, D), jnp.float32)
    for page in pages:
        rows = jnp.asarray(rng.randn(PS, KVH, D), jnp.float32)
        idx = jnp.full((PS,), page, jnp.int32)
        offs = jnp.arange(PS)
        q8 = kv_write(q8, 0, idx, offs, rows)
        f32 = kv_write(f32, 0, idx, offs, rows)
    return q8, f32


def test_ragged_entry_subsumes_decode_chunk_and_ragged():
    """The three legacy ops are exact aliases of the ONE ragged entry
    point, on both impls."""
    rng = np.random.RandomState(0)
    _, f32 = _filled_stores(rng)
    kp = kv_layer(f32, 0)
    B, K, H, D = 3, 2, 4, 16
    tables = jnp.asarray([[1, 2, 3], [2, 3, 0], [0, 0, 0]], jnp.int32)
    lens = jnp.asarray([7, 4, 0], jnp.int32)
    q = jnp.asarray(rng.randn(B, H, D), jnp.float32)
    for impl in ("xla", "pallas"):
        dec = np.asarray(paged_attention(q, kp, kp, tables, lens,
                                         impl=impl))
        rag = np.asarray(ragged_paged_attention(q, kp, kp, tables,
                                                lens, impl=impl))
        np.testing.assert_array_equal(dec, rag)
    qc = jnp.asarray(rng.randn(B, K, H, D), jnp.float32)
    base = jnp.asarray([5, 2, 0], jnp.int32)
    chunk = np.asarray(paged_attention_chunk(qc, kp, kp, tables, base))
    lims = jnp.where(base[:, None] > 0,
                     base[:, None] + jnp.arange(K)[None, :] + 1,
                     0).reshape(-1)
    rag = np.asarray(ragged_paged_attention(
        qc.reshape(B * K, H, D), kp, kp,
        jnp.repeat(tables, K, axis=0), lims))
    np.testing.assert_array_equal(chunk, rag.reshape(B, K, H, D))
    old = np.asarray(paged_attention_ragged(q, kp, kp, tables, lens))
    np.testing.assert_array_equal(
        old, np.asarray(ragged_paged_attention(q, kp, kp, tables,
                                               lens)))


def test_mixed_batch_rows_equal_separate_dispatches():
    """A batch mixing prefill-style rows and decode-style rows gives
    each row EXACTLY what the separate dispatches gave it — the
    property that lets the engine serve both phases in one call."""
    rng = np.random.RandomState(1)
    _, f32 = _filled_stores(rng)
    kp = kv_layer(f32, 0)
    H, D = 4, 16
    # "decode" rows: one token per sequence, full-context limits
    qd = jnp.asarray(rng.randn(2, H, D), jnp.float32)
    td = jnp.asarray([[1, 2, 3], [2, 3, 0]], jnp.int32)
    ld = jnp.asarray([9, 5], jnp.int32)
    # "prefill" rows: successive positions of one sequence
    qp = jnp.asarray(rng.randn(3, H, D), jnp.float32)
    tp = jnp.asarray([[3, 1, 0]] * 3, jnp.int32)
    lp = jnp.asarray([2, 3, 4], jnp.int32)
    sep_d = np.asarray(ragged_paged_attention(qd, kp, kp, td, ld))
    sep_p = np.asarray(ragged_paged_attention(qp, kp, kp, tp, lp))
    mixed = np.asarray(ragged_paged_attention(
        jnp.concatenate([qp, qd]), kp, kp,
        jnp.concatenate([tp, td]), jnp.concatenate([lp, ld])))
    np.testing.assert_array_equal(mixed[:3], sep_p)
    np.testing.assert_array_equal(mixed[3:], sep_d)


def test_int8_within_tolerance_of_f32_reference():
    """int8 quantize-on-write + dequantize-in-kernel stays within the
    documented tolerance of the f32-accumulate reference path, on
    both impls; masked rows stay exactly zero."""
    rng = np.random.RandomState(2)
    q8, f32 = _filled_stores(rng)
    q = jnp.asarray(rng.randn(5, 4, 16), jnp.float32)
    tbl = jnp.asarray(np.tile([[1, 2, 3]], (5, 1)), jnp.int32)
    lens = jnp.asarray([1, 4, 7, 11, 0], jnp.int32)
    ref = np.asarray(ragged_paged_attention_reference(
        q, kv_layer(f32, 0), kv_layer(f32, 0), tbl, lens))
    for impl in ("xla", "pallas", "reference"):
        got = np.asarray(ragged_paged_attention(
            q, kv_layer(q8, 0), kv_layer(q8, 0), tbl, lens,
            impl=impl))
        err = np.max(np.abs(got - ref))
        assert err < INT8_ATOL, (impl, err)
        np.testing.assert_allclose(got[4], 0.0)


def test_quantization_is_deterministic():
    """Identical KV values quantize to identical bytes AND identical
    scales — the property cache-sharing and nonce-pinned replay lean
    on."""
    rng = np.random.RandomState(3)
    rows = jnp.asarray(rng.randn(4, 2, 16), jnp.float32)
    s1 = kv_zeros((1, 8, 4, 2, 16), "int8")
    s2 = kv_zeros((1, 8, 4, 2, 16), "int8")
    idx = jnp.full((4,), 2, jnp.int32)
    offs = jnp.arange(4)
    s1 = kv_write(s1, 0, idx, offs, rows)
    s2 = kv_write(s2, 0, idx, offs, rows)
    np.testing.assert_array_equal(np.asarray(s1.pages),
                                  np.asarray(s2.pages))
    np.testing.assert_array_equal(np.asarray(s1.scales),
                                  np.asarray(s2.scales))


# ---------------------------------------------------------------------------
# engine level: mixed tick parity, int8 parity/tolerance, capacity
# ---------------------------------------------------------------------------


def run_engine(net, prompts, gen, *, n=1, kv=None,
               temperature=0.0, cache=True, page_size=4,
               num_pages=128, chunk=8, seed=3, eos=None,
               max_seqs=4, warm_first=0, impl=None):
    """One engine pass. ``warm_first``: run that many head prompts to
    completion BEFORE the burst (their pages are registered, so the
    burst's shared prefixes genuinely hit the cache)."""
    eng = LLMEngine(net, max_seqs=max_seqs, page_size=page_size,
                    num_pages=num_pages,
                    prefix_cache=cache, prefill_chunk=chunk,
                    eos_token_id=eos, seed=seed,
                    decode_ticks_per_dispatch=n,
                    kv_dtype=kv, attention_impl=impl)
    with eng:
        outs = []
        if warm_first:
            outs += eng.generate(prompts[:warm_first],
                                 max_new_tokens=gen,
                                 temperature=temperature)
        outs += eng.generate(prompts[warm_first:],
                             max_new_tokens=gen,
                             temperature=temperature)
    # leak audit rides every run: the pool is whole after close
    assert len(eng._free_pages) == eng.num_pages - 1, "KV pages leaked"
    return [o["output_ids"] for o in outs], outs, eng


@pytest.mark.parametrize("temperature", [0.0, 0.8],
                         ids=["greedy", "seeded"])
@pytest.mark.parametrize("cache", [True, False],
                         ids=["cache-on", "cache-off"])
def test_mixed_tick_token_identity_across_schedules(cache, temperature):
    """The ISSUE-15 acceptance pin: one batch mixing cache-hit
    prefill (shared prefix), cold prefill chunks and decodes through
    the MIXED tick is token-identical to the same prompts served
    through ONE slot in turn WITHOUT a prefix cache (no decode row ever
    beside a prompt row, no shared page; ``generate`` submits in the
    same order, so the nonces are the same), at N in {1, 8}, with the
    cache on and off; greedy, all of them are ``net.generate``'s."""
    net = tiny_gpt()
    rng = np.random.RandomState(0)
    prefix = rng.randint(0, 97, 8).tolist()          # 2 full pages
    prompts = [prefix + rng.randint(0, 97, 5).tolist(),   # warm
               prefix + rng.randint(0, 97, 3).tolist(),   # cache hit
               rng.randint(0, 97, 21).tolist(),           # cold, long
               rng.randint(0, 97, 4).tolist()]            # cold, short
    ref, _, one = run_engine(net, prompts, 10, max_seqs=1,
                             temperature=temperature, cache=False,
                             warm_first=1)
    assert one.n_mixed_slabs > 0 and one.n_decode_ticks > 0
    if not temperature:
        assert ref == [dense_ref(net, p, 10) for p in prompts]
    for n in (1, 8):
        got, outs, eng = run_engine(net, prompts, 10, n=n,
                                    temperature=temperature,
                                    cache=cache, warm_first=1)
        assert got == ref, f"mixed tick diverged at N={n}"
        assert eng.n_mixed_slabs > 0, "mixed path never engaged"
        assert all(o["ttft_s"] is not None for o in outs)
    if cache:
        assert eng.n_cached_tokens > 0, \
            "shared prefix never hit the cache through the mixed tick"


@pytest.mark.parametrize("kv,temperature,n", [
    (None, 0.0, 1), (None, 0.8, 1), ("int8", 0.0, 4)],
    ids=["f32-pool-greedy", "f32-pool-seeded", "int8-pool-greedy-slab4"])
def test_mixed_tick_kernel_streams_equal_gathered_path(kv, temperature, n):
    """Mixed ticks through the paged-attention KERNEL (chunk rows of
    one prompt sharing a table with rising limits, beside decode rows,
    out of the stacked pool) give the token streams of the gathered
    path: greedy and seeded, and four ticks a dispatch (the kernel
    inside the slab's ``lax.scan``) over an int8 pool."""
    net = tiny_gpt()
    rng = np.random.RandomState(1)
    prefix = rng.randint(0, 97, 8).tolist()
    prompts = [prefix + rng.randint(0, 97, 5).tolist(),
               prefix + rng.randint(0, 97, 3).tolist(),
               rng.randint(0, 97, 21).tolist(),
               rng.randint(0, 97, 4).tolist()]
    ref, _, eng = run_engine(net, prompts, 8, n=n, impl="xla",
                             kv=kv, temperature=temperature,
                             warm_first=1)
    assert eng.attention_impl == "xla"
    got, _, eng = run_engine(net, prompts, 8, n=n,
                             impl="pallas", kv=kv,
                             temperature=temperature, warm_first=1)
    assert eng.attention_impl == "pallas"
    assert eng.n_mixed_slabs > 0, "mixed path never engaged"
    assert got == ref, "kernel streams diverged from the gathered path's"


@pytest.mark.parametrize("impl", ["xla", "pallas"])
@pytest.mark.parametrize("n", [1, 4], ids=["tick", "slab4"])
def test_issue_phases_carry_the_pages_read_and_live(impl, n):
    """``kv_pages_read`` / ``kv_pages_live`` on every ``llm.issue.*``
    phase, from the limits the host packs: the kernel reads each decode
    row's live pages and a tile of prompt rows its sequence's once (a
    tick reads every live page once), the gathered path every table entry
    of every row the program carries."""
    from paddle_tpu.observability import tracing
    net = tiny_gpt()
    rng = np.random.RandomState(2)
    prompts = [rng.randint(0, 97, m).tolist() for m in (21, 5, 13)]
    tracing.clear()
    tracing.enable()
    try:
        _, _, eng = run_engine(net, prompts, 9, n=n, impl=impl,
                               cache=False)
        issues = [s for s in tracing.finished_spans()
                  if s["name"].startswith("llm.issue.")]
    finally:
        tracing.disable()
        tracing.clear()
    kinds = {s["name"] for s in issues}
    assert "llm.issue.mixed" in kinds
    assert kinds & {"llm.issue.decode", "llm.issue.slab"}
    ps, table = eng.page_size, eng.pages_per_seq
    for s in issues:
        a = s["attrs"]
        assert a["kv_pages_live"] > 0, s
        if impl == "xla":
            rows = eng.max_seqs + (eng.prefill_chunk
                                   if s["name"] == "llm.issue.mixed" else 0)
            assert a["kv_pages_read"] == rows * a["ticks"] * table, s
        elif s["name"] == "llm.issue.decode":
            assert a["kv_pages_read"] == a["kv_pages_live"], s
        else:
            assert a["kv_pages_read"] >= a["kv_pages_live"], s
    if impl == "pallas":
        # a prompt's rows in a chunk of 8 are ONE query tile: they fetch
        # their sequence's pages once a tick, where a walk a row would
        # fetch ceil(limit / 4) pages for each of the 21 + 5 + 13 rows
        mixed = [s["attrs"] for s in issues if s["name"] == "llm.issue.mixed"]
        for a in mixed:
            assert a["kv_pages_read"] <= a["ticks"] * a["kv_pages_live"], a
            if n == 1:
                assert a["kv_pages_read"] == a["kv_pages_live"], a
        assert sum(a["kv_pages_read"] for a in mixed) < sum(
            -(-lim // ps) for m in (21, 5, 13) for lim in range(1, m + 1))


def test_attention_impl_follows_the_pools_platform(monkeypatch):
    """Left unset, ``attention_impl`` is what the platform of the
    pool's device calls for: the gathered path on the CPU, the kernel
    on a TPU (a pool whose devices say so); an explicit value is
    honoured wherever the pool lives. The programs' compiler options
    follow the same platform: none off a TPU."""
    from paddle_tpu.inference import llm as llm_mod
    net = tiny_gpt()
    small = dict(max_seqs=2, page_size=4, num_pages=16,
                 prefill_chunk=32)
    with LLMEngine(net, **small) as eng:
        assert eng.attention_impl == "xla"
        assert eng._jit_options == {}
        assert {d.platform for d in eng.k_pages.devices()} == {"cpu"}
    for impl in ("xla", "pallas"):
        with LLMEngine(net, attention_impl=impl, **small) as eng:
            assert eng.attention_impl == impl
    with pytest.raises(ValueError, match="attention_impl"):
        LLMEngine(net, attention_impl="cuda", **small)

    class OnTPU:
        platform = "tpu"

    class Pool:
        def devices(self):
            return {OnTPU()}

    monkeypatch.setattr(llm_mod, "_split_kv", lambda store: (Pool(), None))
    with LLMEngine(net, **small) as eng:
        assert eng.attention_impl == "pallas"
        assert eng._jit_options == {
            "compiler_options": llm_mod._TPU_COMPILER_OPTIONS}


def test_mixed_slab_admits_prefill_without_host_dispatches():
    """A long prompt submitted mid-decode rides INTO the slab: the
    tick history shows mixed slabs ('m'), the chunks are counted, and
    the combined streams are ``net.generate``'s — with
    strictly fewer host dispatches at N = 4 than one tick a dispatch
    needs."""
    net = tiny_gpt()
    rng = np.random.RandomState(6)
    short = rng.randint(0, 97, 4).tolist()
    long = rng.randint(0, 97, 40).tolist()
    want = [dense_ref(net, short, 24), dense_ref(net, long, 8)]

    def interleaved(n):
        eng = LLMEngine(net, max_seqs=2, page_size=4, num_pages=128,
                        prefill_chunk=8, decode_ticks_per_dispatch=n)
        with eng:
            f1 = eng.submit(short, max_new_tokens=24)
            while not (eng.n_decode_ticks or eng.n_mixed_slabs):
                time.sleep(0.002)
            f2 = eng.submit(long, max_new_tokens=8)
            outs = [f1.result(timeout=120), f2.result(timeout=120)]
            hist = "".join(eng.tick_history)
            dispatches = eng.n_host_dispatches
            # 4 + 40 prompt tokens in chunks of 8, all inside mixed slabs
            assert eng.n_prefill_ticks >= 6
        assert len(eng._free_pages) == eng.num_pages - 1
        return [o["output_ids"] for o in outs], hist, dispatches

    ref, _, d_tick = interleaved(1)
    got, hist, d_slab = interleaved(4)
    assert got == ref == want
    assert "m" in hist, hist
    assert d_slab < d_tick, (d_slab, d_tick)


def test_mixed_eos_and_page_pressure():
    """EOS landing mid-slab cuts the stream where ``net.generate``'s
    has the EOS; a pool too small to cover the slab truncates (the
    shrink / truncation decisions re-plan at slab entry, so N = 8
    stops where N = 1 stops): the stream is a prefix of the
    unpressured one, ``truncated`` is set, no page leaks
    (``run_engine`` audits the pool after every run)."""
    net = tiny_gpt()
    rng = np.random.RandomState(1)
    prompts = [rng.randint(0, 97, 5).tolist(),
               rng.randint(0, 97, 7).tolist()]
    dense = [dense_ref(net, p, 12) for p in prompts]
    eos = dense[0][5]
    want = [d[:d.index(eos) + 1] if eos in d else d for d in dense]
    for n in (1, 8):
        got, _, _ = run_engine(net, prompts, 12, n=n, eos=eos)
        assert got == want, n
    assert len(want[0]) < 12 and want[0][-1] == eos
    # page pressure: tiny pool forces shrink/truncation decisions
    tight = [rng.randint(0, 97, 5).tolist()]
    free = [dense_ref(net, tight[0], 20)]
    for pages, cut_short in ((9, True), (16, False)):
        r, routs, _ = run_engine(net, tight, 20, n=1, page_size=2,
                                 num_pages=pages, cache=False)
        g, gouts, _ = run_engine(net, tight, 20, n=8, page_size=2,
                                 num_pages=pages, cache=False)
        assert g == r, pages
        assert g[0] == free[0][:len(g[0])], pages
        assert [o["truncated"] for o in gouts] == \
            [o["truncated"] for o in routs] == [cut_short], pages
        assert (0 < len(g[0]) < 20) == cut_short, pages


def test_mixed_guard_kind_coherent():
    """Satellite: the mixed program registers under its own
    ``mixed_tick`` recompile-guard kind."""
    net = tiny_gpt()
    rng = np.random.RandomState(2)
    prompts = [rng.randint(0, 97, 5).tolist()]
    _, _, eng = run_engine(net, prompts, 8, n=8)
    kinds = {s[0] for s in eng._shape_signatures}
    assert "mixed_tick" in kinds, kinds
    # the realized mixed-slab length tracks the prefill schedule (a
    # short prompt packs into one tick; decode continues in the
    # cheaper pure-decode slab), always within the N bound
    lengths = [s[1] for s in eng._shape_signatures
               if s[0] == "mixed_tick"]
    assert lengths and all(1 <= n <= 8 for n in lengths), lengths


def test_int8_engine_parity_and_tolerance():
    """int8 KV engine: cache on/off, fused slabs (N=8) and the mixed
    tick all produce IDENTICAL int8 streams (quantization is
    deterministic), and greedy agreement vs the f32-pool engine
    meets the documented tolerance."""
    net = tiny_gpt()
    rng = np.random.RandomState(4)
    prefix = rng.randint(0, 97, 8).tolist()
    prompts = [prefix + rng.randint(0, 97, 5).tolist(),
               prefix + rng.randint(0, 97, 3).tolist(),
               rng.randint(0, 97, 11).tolist()]
    base, _, eng = run_engine(net, prompts, 10, kv="int8")
    assert isinstance(eng.k_pages, QuantizedKV)
    for kwargs in (dict(cache=False), dict(n=8)):
        got, _, _ = run_engine(net, prompts, 10, kv="int8", **kwargs)
        assert got == base, f"int8 streams diverged under {kwargs}"
    f32, _, _ = run_engine(net, prompts, 10)
    agree = np.mean([np.mean([a == b for a, b in zip(x, y)])
                     for x, y in zip(base, f32)])
    assert agree >= INT8_GREEDY_AGREE, (
        f"int8 greedy agreement {agree:.3f} below the documented "
        f"tolerance {INT8_GREEDY_AGREE}")


def test_int8_capacity_and_ledger_split():
    """~2x page capacity at fixed HBM: int8 page bytes (scale table
    included) are <= 1/1.8 of bf16's; the memory ledger's kv_pool
    rows gain the dtype/scale split and still tile the pool
    exactly."""
    from paddle_tpu.core import flags as _flags
    from paddle_tpu.observability import memory as memobs
    net = tiny_gpt()
    engines = {}
    for kv in ("bf16", "int8"):
        engines[kv] = LLMEngine(net, max_seqs=2, page_size=4,
                                num_pages=32, prefill_chunk=16,
                                kv_dtype=kv)
    try:
        ratio = engines["bf16"]._page_bytes / \
            engines["int8"]._page_bytes
        assert ratio >= 1.8, (
            f"int8 pages must buy >=1.8x capacity at fixed HBM; "
            f"page bytes give only {ratio:.2f}x")
        eng = engines["int8"]
        assert eng._page_scale_bytes > 0
        if memobs.enabled():
            rows = [r for r in memobs.instance().rows()
                    if r["owner"] == "kv_pool"]
            kinds = {r["kind"] for r in rows}
            assert "scale_table" in kinds, kinds
            total = sum(r["bytes"] for r in rows)
            # one engine is bf16 (no scale row), one int8: each
            # engine's rows tile ITS pool; sum over both
            expect = sum(e.num_pages * e._page_bytes
                         for e in engines.values())
            assert total == expect, (total, expect)
    finally:
        for e in engines.values():
            e.close()


def test_kv_dtype_knob_validation():
    net = tiny_gpt()
    with pytest.raises(ValueError, match="kv_dtype"):
        LLMEngine(net, max_seqs=2, page_size=4, num_pages=16,
                  prefill_chunk=16, kv_dtype="int4")
    pt.seed(1)
    dcfg = gpt_config("gpt2-small", num_layers=1, hidden_size=32,
                      num_heads=2, vocab_size=97,
                      max_position_embeddings=96, hidden_dropout=0.0,
                      attention_dropout=0.0)
    draft = GPTForCausalLM(dcfg)
    # int8 + draft_net composes (the quantized draft pool), and a
    # speculative engine keeps its slab width
    eng = LLMEngine(net, max_seqs=2, page_size=4, num_pages=32,
                    prefill_chunk=16, draft_net=draft,
                    kv_dtype="int8", decode_ticks_per_dispatch=4)
    assert eng.spec_k and isinstance(eng.draft_k_pages, QuantizedKV)
    assert eng.decode_ticks_per_dispatch == 4
    eng.close()
    # flags feed the defaults
    from paddle_tpu.core import flags
    flags.set_flags({"kv_dtype": "int8"})
    try:
        eng = LLMEngine(net, max_seqs=2, page_size=4, num_pages=16,
                        prefill_chunk=16)
        assert eng.kv_dtype == "int8"
        assert isinstance(eng.k_pages, QuantizedKV)
        eng.close()
    finally:
        flags.set_flags({"kv_dtype": ""})


def _tiny_model(name):
    """``(net, vocabulary, engine options)`` of a tiny model of each
    architecture the engine serves, as that model's own tests build it."""
    if name == "gpt":
        return tiny_gpt(), 97, dict(page_size=4, num_pages=128,
                                    prefill_chunk=32)
    import importlib
    net = importlib.import_module(f"test_{name}").build()[0]
    return net, 128, dict(page_size=8, num_pages=64, max_len=128,
                          prefill_chunk=16, kv_dtype="f32")


@pytest.mark.parametrize("model", ["gpt", "granite_hybrid", "ouro", "laguna",
                                   "kimi_linear", "mimo_v2"])
def test_kernel_mixed_ticks_equal_separate_prefill_and_decode(model):
    """The mixed-tick pin THROUGH THE KERNEL, for every architecture: a
    mixed program with live decode rows (its chunk rows through query
    tiles, its decode rows through the row walk, one call a layer) serves
    token for token what ONE slot serves in turn: a mixed program without
    a decode row (all tiles: the prefill program), then decode programs
    (``n_chunk`` 0: the row walk alone). Prompts that share chunks, a
    prompt longer than two chunks, a prompt of one token."""
    net, vocab, engine = _tiny_model(model)
    rng = np.random.RandomState(4)
    prompts = [rng.randint(0, vocab, m).tolist() for m in (21, 1, 37, 6)]
    streams = {}
    for slots in (1, 3):
        with LLMEngine(net, max_seqs=slots, attention_impl="pallas",
                       **engine) as eng:
            assert eng.attention_impl == "pallas"
            outs = eng.generate(prompts, max_new_tokens=8)
            assert "m" in eng.tick_history and "d" in eng.tick_history
        streams[slots] = [o["output_ids"] for o in outs]
        assert all(len(s) == 8 for s in streams[slots])
    assert streams[3] == streams[1]
