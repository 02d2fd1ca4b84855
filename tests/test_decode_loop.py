"""Device-resident decode loop (ISSUE 10): N decode ticks fused into
ONE ``lax.scan`` dispatch (`LLMEngine(decode_ticks_per_dispatch=N)`).

Contract under test: fused slabs are TOKEN-IDENTICAL to the per-tick
path (N=1) — greedy and seeded sampling, prefix cache on or off,
EOS/length finishing mid-slab, page boundaries crossed inside a slab,
slabs interleaved with chunked prefill — because the scan body IS the
per-tick program and sampling keys fold (nonce, position) only.
Failure semantics degrade by at most one slab: cancel/deadline
submitted mid-slab resolve at the slab boundary with their KV pages
freed. N=1 must keep the per-tick program (no scan op compiled)."""

import time

import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu.inference.llm import (DecodeCarry, LLMEngine,
                                      RequestCancelled)
from paddle_tpu.models.gpt import GPTForCausalLM, gpt_config
from paddle_tpu.reliability.retry import DeadlineExceeded


def tiny_gpt():
    pt.seed(0)
    cfg = gpt_config("gpt2-small", num_layers=2, hidden_size=64,
                     num_heads=4, vocab_size=97,
                     max_position_embeddings=96, hidden_dropout=0.0,
                     attention_dropout=0.0)
    return GPTForCausalLM(cfg)


def run(net, prompts, gen, n, *, temperature=0.0, cache=True,
        eos=None, page_size=4, num_pages=128, chunk=16, seed=0,
        max_seqs=4):
    eng = LLMEngine(net, max_seqs=max_seqs, page_size=page_size,
                    num_pages=num_pages,
                    prefix_cache=cache, prefill_chunk=chunk,
                    eos_token_id=eos, seed=seed,
                    decode_ticks_per_dispatch=n)
    with eng:
        outs = eng.generate(prompts, max_new_tokens=gen,
                            temperature=temperature)
    # leak audit rides every parity run: the pool is whole after close
    assert len(eng._free_pages) == eng.num_pages - 1, \
        f"KV pages leaked at N={n}"
    return outs, eng


@pytest.mark.parametrize("temperature", [0.0, 0.8],
                         ids=["greedy", "seeded"])
@pytest.mark.parametrize("cache", [True, False],
                         ids=["cache-on", "cache-off"])
def test_token_identity_across_n(cache, temperature):
    """N ∈ {1, 4, 8} × prefix cache on/off × greedy/seeded sampling:
    fused slabs reproduce the per-tick stream exactly."""
    net = tiny_gpt()
    rng = np.random.RandomState(0)
    prompts = [rng.randint(0, 97, n).tolist() for n in (5, 11, 3)]
    ref, _ = run(net, prompts, 10, 1, temperature=temperature,
                 cache=cache, seed=3)
    for n in (4, 8):
        got, eng = run(net, prompts, 10, n, temperature=temperature,
                       cache=cache, seed=3)
        assert [o["output_ids"] for o in got] == \
            [o["output_ids"] for o in ref], f"stream diverged at N={n}"
        assert not any(o["truncated"] for o in got)
        # the knob did what it says: fewer host dispatches than ticks
        assert eng.n_host_dispatches < eng.n_decode_ticks


def test_mid_slab_eos_masking():
    """A slot hitting EOS mid-slab stops there: ticks past its EOS
    are masked no-ops on device (budget zeroed), the host never
    surfaces them, and the stream equals N=1 with the same EOS."""
    net = tiny_gpt()
    rng = np.random.RandomState(1)
    prompts = [rng.randint(0, 97, 5).tolist(),
               rng.randint(0, 97, 7).tolist()]
    # pick an eos each prompt actually emits mid-generation at N=1
    base, _ = run(net, prompts, 12, 1)
    eos = base[0]["output_ids"][5]
    ref, _ = run(net, prompts, 12, 1, eos=eos)
    got, eng = run(net, prompts, 12, 8, eos=eos)
    assert [o["output_ids"] for o in got] == \
        [o["output_ids"] for o in ref]
    # prompt 0 genuinely finished early (mid-slab), not at the limit
    assert len(got[0]["output_ids"]) < 12
    assert got[0]["output_ids"][-1] == eos


def test_page_boundary_crossing_inside_slab():
    """page_size=2 with N=8: every slab crosses multiple page
    boundaries; pre-reservation at slab entry keeps the scan body
    shape-stable and the stream identical to N=1."""
    net = tiny_gpt()
    rng = np.random.RandomState(2)
    prompts = [rng.randint(0, 97, 5).tolist()]
    ref, _ = run(net, prompts, 16, 1, page_size=2, num_pages=64)
    got, _ = run(net, prompts, 16, 8, page_size=2, num_pages=64)
    assert got[0]["output_ids"] == ref[0]["output_ids"]
    assert not got[0]["truncated"]


def test_slab_shrinks_under_page_pressure():
    """A pool too small to pre-reserve N tokens shrinks the slab to
    the coverable boundary instead of truncating: the request still
    completes (or truncates) exactly as N=1 does."""
    net = tiny_gpt()
    rng = np.random.RandomState(3)
    prompts = [rng.randint(0, 97, 5).tolist()]
    # single request: prompt needs 3 pages (ps=2), generation wants
    # 20 more tokens through a pool holding only 16 positions — the
    # second slab can cover just 3 of its 8 ticks (pool dry at the
    # 9th page), so it must shrink, and the request then truncates
    # exactly where N=1 does
    for pages in (9, 16):
        ref, _ = run(net, prompts, 20, 1, page_size=2,
                     num_pages=pages, cache=False)
        got, eng = run(net, prompts, 20, 8, page_size=2,
                       num_pages=pages, cache=False)
        assert got[0]["output_ids"] == ref[0]["output_ids"], pages
        assert got[0]["truncated"] == ref[0]["truncated"], pages
        if pages == 9:
            # the tight pool really did force shrunk slabs: more than
            # one distinct decode_loop signature compiled
            loops = [s for s in eng._shape_signatures
                     if s[0] == "decode_loop"]
            assert len(loops) > 1, loops


def test_max_new_tokens_not_multiple_of_slab():
    """gen_len % N != 0: the tail slab runs with a partial budget
    (masked ticks beyond it) and emits exactly the requested count."""
    net = tiny_gpt()
    rng = np.random.RandomState(4)
    prompts = [rng.randint(0, 97, 5).tolist()]
    ref, _ = run(net, prompts, 10, 1)
    got, eng = run(net, prompts, 10, 8)
    assert got[0]["output_ids"] == ref[0]["output_ids"]
    assert len(got[0]["output_ids"]) == 10
    # one compiled slab program serves both full and partial slabs
    # (budgets are data, not shapes)
    assert [s for s in eng._shape_signatures
            if s[0] == "decode_loop"] == [("decode_loop", 8)]


def test_cancel_and_deadline_resolve_within_slab_boundary():
    """Cancel/deadline submitted mid-slab resolve at the next slab
    boundary (not after the full generation) and free their pages."""
    net = tiny_gpt()
    eng = LLMEngine(net, max_seqs=2, page_size=4, num_pages=64,
                    prefill_chunk=16,
                    decode_ticks_per_dispatch=8)
    with eng:
        rng = np.random.RandomState(5)
        fut = eng.submit(rng.randint(0, 97, 5).tolist(),
                         max_new_tokens=80)
        while eng.n_decode_ticks < 8:     # generation underway
            time.sleep(0.005)
        assert eng.cancel(fut.request_id)
        with pytest.raises(RequestCancelled):
            fut.result(timeout=60)
        ticks_at_cancel = eng.n_decode_ticks
        # an expired deadline resolves typed at the next boundary —
        # hopeless by construction (the chaos-soak idiom): a small-
        # but-positive budget races the slab wall clock and a warm
        # engine can legitimately finish 80 tokens inside it
        fut2 = eng.submit(rng.randint(0, 97, 5).tolist(),
                          max_new_tokens=80, deadline=-1.0)
        with pytest.raises(DeadlineExceeded):
            fut2.result(timeout=60)
        # the cancelled request stopped within ~one slab of the
        # cancel (the loop never ran fut's remaining ~70 tokens)
        assert eng.n_decode_ticks < ticks_at_cancel + 8 + 70
    assert len(eng._free_pages) == eng.num_pages - 1, "pages leaked"


def test_fused_ticks_interleave_with_chunked_prefill(issue_phases):
    """A long prompt admitted mid-decode prefills in chunks INSIDE mixed
    slabs that carry the short request's decode rows: the short request
    RECEIVES tokens from dispatches that carried the long prompt's chunks,
    and both streams match the dense reference at N = 1 and 4."""
    from paddle_tpu.observability import tracing
    net = tiny_gpt()
    rng = np.random.RandomState(6)
    short = rng.randint(0, 97, 4).tolist()
    long = rng.randint(0, 97, 40).tolist()
    want = [np.asarray(net.generate(jnp.asarray([p]), max_new_tokens=g)
                       )[0, len(p):].tolist()
            for p, g in ((short, 24), (long, 8))]
    for n in (1, 4):
        tracing.clear()
        tracing.enable()
        with LLMEngine(net, max_seqs=2, page_size=4, num_pages=128,
                       prefill_chunk=8,
                       decode_ticks_per_dispatch=n) as eng:
            f1 = eng.submit(short, max_new_tokens=24)
            while not eng.n_decode_ticks:   # f1 decoding
                time.sleep(0.002)
            f2 = eng.submit(long, max_new_tokens=8)
            got = [f1.result(timeout=120), f2.result(timeout=120)]
            hist = "".join(eng.tick_history)
            slabs, chunks = eng.n_mixed_slabs, eng.n_prefill_ticks
        tracing.disable()
        assert len(eng._free_pages) == eng.num_pages - 1
        assert [o["output_ids"] for o in got] == want
        # the short prompt's chunk, then the long prompt's five (40 / 8);
        # N = 1: a mixed dispatch a chunk; N = 4: a slab holds up to four
        assert chunks >= 6 and slabs >= (6 if n == 1 else 3), (slabs, hist)
        beside = issue_phases.tokens_beside_chunks(
            tracing.finished_spans(), len(long))
        assert len(beside) >= 2 and sum(beside.values()) >= 5, (beside, hist)


def test_n1_compiles_zero_scan_ops():
    """The HLO pin (PR 9 discipline): at N=1 the engine keeps the
    per-tick program — the slab jit is NEVER traced (zero scan
    programs compiled), and the per-tick decode HLO carries only the
    RNG's internal loops. Positive control: the N>1 slab program adds
    EXACTLY ONE loop op over the per-tick body — the scan."""
    net = tiny_gpt()
    rng = np.random.RandomState(7)
    prompts = [rng.randint(0, 97, 5).tolist()]
    _, eng1 = run(net, prompts, 8, 1)
    assert not any(s[0] == "decode_loop"
                   for s in eng1._shape_signatures)
    assert eng1._slab_fn._cache_size() == 0, \
        "N=1 engine compiled a slab program"
    b = eng1.max_seqs
    zeros = jnp.zeros((b,), jnp.int32)
    host_zeros = np.zeros((b,), np.int32)
    tick_hlo = eng1._decode_fn.lower(
        eng1._params, eng1._buffers, zeros,
        eng1._stage_decode(host_zeros, host_zeros),
        eng1.k_pages, eng1.v_pages, eng1._key).as_text()

    _, eng4 = run(net, prompts, 8, 4)
    carry = DecodeCarry(
        tokens=zeros, positions=zeros, budgets=zeros,
        k_pages=eng4.k_pages, v_pages=eng4.v_pages)
    slab_hlo = eng4._slab_fn.lower(
        eng4._params, eng4._buffers, carry,
        jnp.zeros((b, eng4.pages_per_seq), jnp.int32),
        jnp.zeros((b,), jnp.float32), zeros, eng4._key, 4).as_text()
    n_tick = tick_hlo.count("stablehlo.while")
    n_slab = slab_hlo.count("stablehlo.while")
    assert n_slab == n_tick + 1, (
        f"slab program should add exactly the scan loop over the "
        f"per-tick body: {n_tick} vs {n_slab} while ops")


def test_recompile_guard_counts_slab_kinds_separately():
    """Satellite: decode_loop signatures are their own kind — an
    N-knob sweep adds decode_loop entries without consuming
    decode_step ones, so the 4096 cap can't be blown silently."""
    net = tiny_gpt()
    rng = np.random.RandomState(8)
    prompts = [rng.randint(0, 97, 5).tolist()]
    _, eng1 = run(net, prompts, 6, 1)
    kinds1 = {s[0] for s in eng1._shape_signatures}
    assert "decode_step" in kinds1 and "decode_loop" not in kinds1
    _, eng8 = run(net, prompts, 6, 8)
    kinds8 = {s[0] for s in eng8._shape_signatures}
    assert "decode_loop" in kinds8 and "decode_step" not in kinds8
    assert ("decode_loop", 8) in eng8._shape_signatures


def test_flag_default_feeds_engine():
    from paddle_tpu.core import flags
    net = tiny_gpt()
    flags.set_flags({"decode_ticks_per_dispatch": 4})
    try:
        eng = LLMEngine(net, max_seqs=2, page_size=4, num_pages=64,
                        prefill_chunk=16)
        assert eng.decode_ticks_per_dispatch == 4
        eng.close()
    finally:
        flags.set_flags({"decode_ticks_per_dispatch": 1})


def test_inline_prefill_first_token_is_async():
    """Satellite: the speculative (inline-prefill) path no longer
    blocks on int(nxt) at admission — the first token arrives through
    the drain, TTFT is observed at fetch, and a 1-token request
    resolves through the drain path."""
    pt.seed(0)
    cfg = gpt_config("gpt2-small", num_layers=2, hidden_size=64,
                     num_heads=4, vocab_size=97,
                     max_position_embeddings=96, hidden_dropout=0.0,
                     attention_dropout=0.0)
    net = GPTForCausalLM(cfg)
    pt.seed(1)
    dcfg = gpt_config("gpt2-small", num_layers=1, hidden_size=32,
                      num_heads=2, vocab_size=97,
                      max_position_embeddings=96, hidden_dropout=0.0,
                      attention_dropout=0.0)
    draft = GPTForCausalLM(dcfg)
    rng = np.random.RandomState(9)
    prompts = [rng.randint(0, 97, 6).tolist()]
    want = [np.asarray(net.generate(jnp.asarray([p]),
                                    max_new_tokens=8))[0, len(p):]
            .tolist() for p in prompts]
    eng = LLMEngine(net, max_seqs=2, page_size=4, num_pages=64,
                    prefill_chunk=16, draft_net=draft,
                    spec_tokens=3)
    with eng:
        outs = eng.generate(prompts, max_new_tokens=8)
        assert outs[0]["output_ids"] == want[0]
        assert outs[0]["ttft_s"] is not None
        # the 1-token edge: the only token rides the drain
        one = eng.generate(prompts, max_new_tokens=1)
        assert one[0]["output_ids"] == want[0][:1]
    assert len(eng._free_pages) == eng.num_pages - 1
