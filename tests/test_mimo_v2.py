"""models/mimo_v2.py against the plain reference
(benchmark/reference/mimo_v2.py: float32, a whole masked attention a layer
with the sink written out, no pages) on seeded weights, at a small size on
the CPU (6 layers: full, four sliding, full; window 8 UNDER a prompt chunk of
16; 8 query heads over 1 K/V head on a full layer and 2 on a sliding one,
keys of 24 beside values of 16, a sink a head on the sliding layers; a dense
layer then 8 sigmoid-routed experts, 2 a token, no shared one): the
whole-sequence forward, and the served path through ``LLMEngine``: prompts in
chunks TWICE THE WINDOW through both cache groups (K pages stored 128 wide
beside V pages of 16), then decode with the window group's pages released
behind it.

Tolerances: float32 throughout, so what separates the program from the
reference is the order of float32 sums: logits of up to 0.6 agree to
TOL = 5e-6 (the issue asks for 1e-4)."""
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from benchmark import weights_mimo
from benchmark.reference import mimo_v2 as ref
from paddle_tpu.inference import page_pool
from paddle_tpu.inference.llm import CacheGroupUnsupported, LLMEngine
from paddle_tpu.models import MiMoV2Config, MiMoV2ForCausalLM
from paddle_tpu.models.gpt import GPTForCausalLM, gpt_config
from paddle_tpu.observability import server as dbgsrv

TOL = 5e-6
WINDOW, CHUNK, PAGE = 8, 16, 4
TINY = dict(
    vocab_size=128, hidden_size=64, intermediate_size=96,
    num_hidden_layers=6, num_attention_heads=8, num_key_value_heads=1,
    head_dim=24, v_head_dim=16, swa_num_attention_heads=8,
    swa_num_key_value_heads=2, swa_head_dim=24, swa_v_head_dim=16,
    layernorm_epsilon=1e-5, rope_theta=5000000, swa_rope_theta=10000,
    partial_rotary_factor=0.334, sliding_window=WINDOW,
    attention_value_scale=0.707, hybrid_layer_pattern=[0, 1, 1, 1, 1, 0],
    add_swa_attention_sink_bias=True, add_full_attention_sink_bias=False,
    moe_layer_freq=[0, 1, 1, 1, 1, 1], moe_intermediate_size=32,
    n_routed_experts=8, n_shared_experts=None, num_experts_per_tok=2,
    norm_topk_prob=True, scoring_func="sigmoid", n_group=1, topk_group=1,
    topk_method="noaux_tc", routed_scaling_factor=None)
ENGINE = dict(page_size=PAGE, num_pages=96, max_len=128, prefill_chunk=CHUNK,
              kv_dtype="f32")
RING = -(-(WINDOW + CHUNK) // PAGE) + 1


def build(seed=5, **over):
    """``(net, params, dims)`` around the benchmark's seeded arrays."""
    model = dict(TINY, **over)
    d = weights_mimo.dims_of(model)
    params = weights_mimo.make(d, seed, jnp.float32)
    pt.seed(0)
    net = MiMoV2ForCausalLM(MiMoV2Config(
        **{"max_position_embeddings": 256, **model}))
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
    """57 positions: seven windows, so the sliding mask cuts and the sink
    stands beside as few as one score."""
    net, params, d = model
    ids = jnp.asarray(np.random.default_rng(0).integers(0, 128, (2, 57)),
                      jnp.int32)
    want = ref.logits(params, ids, d)
    assert float(jnp.max(jnp.abs(want))) > 0.3
    np.testing.assert_allclose(net(ids), want, atol=TOL, rtol=TOL)
    # the window matters: a model that saw everything answers otherwise
    wide, _, _ = build(sliding_window=256)
    assert float(jnp.max(jnp.abs(wide(ids) - want))) > 1e-3


@pytest.mark.parametrize("quant", ["no_sink", "no_vscale", "fp8", "bf16",
                                   "fp8+no_sink"])
def test_each_control_moves_the_logits_past_the_tolerance(model, quant):
    """The sink left out, the value scale left out, a lower precision: each
    moves the reference's own logits by far more than the program lies from
    them."""
    _, params, d = model
    ids = jnp.asarray(np.random.default_rng(0).integers(0, 128, (1, 37)),
                      jnp.int32)
    moved = float(jnp.max(jnp.abs(ref.logits(params, ids, d, quant)
                                  - ref.logits(params, ids, d))))
    assert moved > 100 * TOL, (quant, moved)
    assert moved > 1e-4


def test_two_cache_groups_with_their_own_widths_lifetimes_and_a_sink(model):
    net, _, _ = model
    assert [tuple(g) for g in net.kv_cache_spec()] == [
        ("full", 2, 1, 128, None, None, 16, False),
        ("window", 4, 2, 128, WINDOW, None, 16, True)]
    assert net.moe_aux_spec() == (5, 8) and net.experts_held == (0, 8)
    assert net.state_cache_spec() is None and net.loop_aux_spec() is None
    # the published geometry, from the defaults
    cfg = MiMoV2Config(num_hidden_layers=10)
    assert cfg.layers_of("full") == (0, 5)
    assert [cfg.key_width(k) for k in ("full", "sliding")] == [256, 256]
    assert cfg.geometry("full")[:4] == (64, 4, 192, 128)
    assert cfg.geometry("sliding")[:4] == (64, 8, 192, 128)
    assert cfg.geometry("sliding")[5:] == (128, True)
    assert cfg.rope("full", jnp.arange(3))[0].shape == (3, 64)
    assert MiMoV2Config().layers_of("full") == (0, 5, 11, 17, 23, 29, 35,
                                                41, 47)


@pytest.mark.parametrize("knobs", [dict(), dict(max_seqs=1),
                                   dict(attention_impl="pallas")],
                         ids=["mixed_ticks", "one_slot", "kernel"])
def test_engine_holds_to_the_reference_with_a_window_under_the_chunk(
        model, knobs):
    """Prompts of several chunks, each chunk twice the window (so a chunk's
    first rows lie behind the window of its last rows inside one program),
    shorter ones, joins at different times (3 slots, 5 requests), then 24
    tokens of decode. Every served token within TOL of the reference's best
    and what ``generate`` gives; the window group never holds more than its
    ring a slot, and released pages are POISONED as they go back to the
    free list, so a read of one would show. ``one_slot``: all five through
    ONE slot in turn, the window's ring handed from each to the next."""
    net, params, d = model
    prompts = prompts_of((70, 45, 9, 30, 61))
    release = page_pool.PagePool.release_behind
    peak = {"window": 0, "full": 0}

    def poisoning_release(pool, slot, next_position):
        g = pool.groups[1]
        before = set(int(p) for p in g.tables[slot] if p > 0)
        n = release(pool, slot, next_position)
        gone = sorted(before - set(int(p) for p in g.tables[slot]))
        if gone:
            idx = jnp.asarray(gone)
            g.k_pages = g.k_pages.at[:, idx].set(1e4)
            g.v_pages = g.v_pages.at[:, idx].set(1e4)
        for gg in pool.groups:
            peak[gg.name] = max(peak[gg.name], int(gg.held.max()))
        return n

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(page_pool.PagePool, "release_behind", poisoning_release)
        with LLMEngine(net, **{"max_seqs": 3, **ENGINE, **knobs}) as eng:
            futs = [eng.submit(p, max_new_tokens=24) for p in prompts[:4]]
            outs = [f.result(timeout=900) for f in futs]
            outs.append(eng.submit(prompts[4], max_new_tokens=24)
                        .result(timeout=900))
            full, window = eng._pool.groups
            assert window.ring == RING and full.ring is None
            assert window.n_released > 0 and full.n_released == 0
            assert WINDOW // PAGE < peak["window"] <= RING
            assert peak["full"] == -(-(70 + 24) // PAGE)
            # the slots drained: every page is back on its free list
            assert len(full.free) == full.num_pages - 1
            assert len(window.free) == window.num_pages - 1
            assert window.num_pages == eng.max_seqs * RING + 1
            assert eng.moe_rows_by_expert.shape == (5, 8)
            assert full.k_pages.shape[-2:] == (1, 128) \
                and full.v_pages.shape[-2:] == (1, 16)
            assert window.k_pages.shape[-2:] == (2, 128) \
                and window.v_pages.shape[-2:] == (2, 16)
    for p, o in zip(prompts, outs):
        toks = list(o["output_ids"])
        assert len(toks) == 24 and not o["truncated"]
        assert served_gap(params, d, p, toks) <= TOL
        want = np.asarray(net.generate(jnp.asarray([p], jnp.int32), 24))
        assert toks == want[0, len(p):].tolist()


def test_engine_serves_a_share_of_the_experts():
    """Experts 2-5 of 8 held: the engine against the reference's same
    share, and the counters say what fell on the held ones."""
    net, params, d = build(experts_held=(2, 4))
    assert net.experts_held == (2, 4)
    prompts = prompts_of((33, 12), seed=2)
    with LLMEngine(net, max_seqs=2, **ENGINE) as eng:
        outs = [f.result(timeout=600) for f in
                [eng.submit(p, max_new_tokens=12) for p in prompts]]
        assert eng.moe_rows_by_expert.shape == (5, 4)
        assert 0 < eng.n_moe_pairs_held < eng.n_moe_pairs
    for p, o in zip(prompts, outs):
        assert served_gap(params, d, p, list(o["output_ids"])) <= TOL


def test_the_shares_of_a_routed_layer_add_up_to_the_uncut_layer(model):
    """The guide's section 4: every chip scores all experts and takes the
    top k, the held ones add. Four shares of two experts each (the cell:
    sixteen of sixteen), in the reference and in the program's layer, sum
    to the reference's uncut routed layer."""
    net, params, d = model
    lp = {k[len("layers.2."):]: v for k, v in params.items()
          if k.startswith("layers.2.")}
    u = jnp.asarray(np.random.default_rng(3).normal(size=(11, 64)),
                    jnp.float32)
    whole = ref.routed(u, lp, d, None, held=(0, 8))
    assert float(jnp.max(jnp.abs(whole))) > 1e-3
    shares = [ref.routed(u, {**lp, "moe.w_in": lp["moe.w_in"][f:f + 2],
                             "moe.w_out": lp["moe.w_out"][f:f + 2]},
                         d, None, held=(f, 2)) for f in range(0, 8, 2)]
    np.testing.assert_allclose(sum(shares), whole, atol=1e-6, rtol=1e-6)
    mine = []
    for f in range(0, 8, 2):
        part, _, _ = build(experts_held=(f, 2),
                           n_routed_experts=8)
        moe = part.layers[2].moe
        moe.set_state_dict({
            "router": lp["moe.router"], "e_bias": lp["moe.e_bias"],
            "w_in": lp["moe.w_in"][f:f + 2],
            "w_out": lp["moe.w_out"][f:f + 2]})
        y, held = moe(u)
        mine.append(y)
        assert int(held.sum()) > 0
    np.testing.assert_allclose(sum(mine), whole, atol=1e-6, rtol=1e-6)


def _draft():
    pt.seed(0)
    return GPTForCausalLM(gpt_config("gpt2-small", num_layers=1,
                                     hidden_size=32, num_heads=2,
                                     vocab_size=128))


def test_the_refused_modes_refuse_by_name_and_statusz_says_widths_and_sink(
        model):
    net, _, _ = model
    for knobs, mechanism in ((dict(draft_net=_draft()), "speculative_verify"),
                             (dict(decode_ticks_per_dispatch=2),
                              "fused_slab"),
                             (dict(kv_dtype="int8"), "int8_pages")):
        with pytest.raises(CacheGroupUnsupported) as e:
            LLMEngine(net, max_seqs=2, **dict(ENGINE, **knobs))
        assert e.value.mechanism == mechanism
    with LLMEngine(net, max_seqs=2, **ENGINE, prefix_cache=True) as eng:
        assert eng._cache is None
        for call in (lambda: eng.export_pages([]),
                     lambda: eng.import_pages({})):
            with pytest.raises(CacheGroupUnsupported) as e:
                call()
            assert e.value.mechanism == "kv_page_migration"
        status = dbgsrv._collect_status()[eng._status_name]
        assert [(g["name"], g["layers"], g["kv_heads"], g["head_dim"],
                 g["v_head_dim"], g["sink"], g["window"], g["ring_pages"],
                 g["k_row_bytes"], g["v_row_bytes"], g["row_bytes"])
                for g in status["cache_groups"]] == [
            ("full", 2, 1, 128, 16, False, None, None,
             2 * 128 * 4, 2 * 16 * 4, 2 * 144 * 4),
            ("window", 4, 2, 128, 16, True, WINDOW, RING,
             4 * 2 * 128 * 4, 4 * 2 * 16 * 4, 4 * 2 * 144 * 4)]
        assert set(status["cache_groups_unsupported"]) == {
            "prefix_reuse", "kv_page_migration", "speculative_verify",
            "fused_slab"}
        assert status["prefix_cache"]["enabled"] is False


def test_the_issue_phases_say_each_groups_bytes_at_the_stored_widths(model):
    """While tracing: ``kv_groups`` has, a group, what it read and holds and
    the bytes of a row of K and of V as stored; a long sequence's window
    group reads and holds its window only."""
    from paddle_tpu.observability import tracing
    net, _, _ = model
    tracing.enable()
    try:
        tracing.clear()
        with LLMEngine(net, max_seqs=2, **ENGINE,
                       attention_impl="pallas") as eng:
            eng.submit(prompts_of((70,))[0], max_new_tokens=20) \
                .result(timeout=600)
            page_bytes = {g.name: g.page_bytes for g in eng._pool.groups}
        spans = [s for s in tracing.finished_spans()
                 if s["name"] == "llm.issue.decode"]
    finally:
        tracing.disable()
    last = spans[-1]["attrs"]
    groups = last["kv_groups"]
    assert groups["full"]["read"] == groups["full"]["live"] == \
        -(-last["context_tokens"] // PAGE)
    assert groups["window"]["live"] <= WINDOW // PAGE + 1
    assert (groups["window"]["k_row_bytes"],
            groups["window"]["v_row_bytes"]) == (4 * 2 * 128 * 4,
                                                 4 * 2 * 16 * 4)
    assert (groups["full"]["k_row_bytes"]
            + groups["full"]["v_row_bytes"]) * PAGE == page_bytes["full"]
    assert groups["full"]["bytes_held"] \
        == groups["full"]["live"] * page_bytes["full"]
    assert sum(s["attrs"]["window_pages_released"] for s in spans) > 0


def test_config_refuses_what_the_model_does_not_compute():
    for over in (dict(scoring_func="softmax"), dict(n_group=2),
                 dict(n_shared_experts=1), dict(norm_topk_prob=False)):
        with pytest.raises(NotImplementedError):
            MiMoV2Config(**over)
    with pytest.raises(ValueError):
        MiMoV2Config(num_hidden_layers=49)


@pytest.mark.parametrize("impl", ["xla", "pallas"], ids=["gathered", "kernel"])
def test_a_256_row_chunk_over_window_128_is_the_uncached_forward(impl):
    """The cell's own numbers: window 128 under chunks of 256 at pages of
    16, a ring of 25 pages. A prompt of 600 tokens is two whole chunks and
    a rest: inside each, rows of the first tile lie two windows behind the
    last tile's, and a tile of 32 rows spans a quarter of a window. Served
    tokens are the reference's best to TOL and ``generate``'s."""
    net, params, d = build(
        sliding_window=128, num_hidden_layers=3,
        hybrid_layer_pattern=[0, 1, 1], moe_layer_freq=[0, 1, 1],
        max_position_embeddings=1024)
    prompts = prompts_of((600, 150), seed=4)
    with LLMEngine(net, max_seqs=2, page_size=16, num_pages=96,
                   max_len=640, prefill_chunk=256, kv_dtype="f32",
                   attention_impl=impl) as eng:
        outs = [f.result(timeout=900) for f in
                [eng.submit(p, max_new_tokens=6) for p in prompts]]
        window = eng._pool.groups[1]
        assert window.ring == 25 and window.n_released >= (600 - 128) // 16
    for p, o in zip(prompts, outs):
        toks = list(o["output_ids"])
        seq = p + toks + [0] * (-(len(p) + len(toks)) % 256)
        served = np.zeros((1, len(seq)), np.int32)
        served[0, len(p) - 1:len(p) + len(toks) - 1] = toks
        got = ref.served_gaps(params, np.asarray([seq], np.int32),
                              np.asarray([len(p) - 1]),
                              np.asarray([len(toks)]), served, d)
        assert float(np.max(np.asarray(got["gap"]))) <= TOL
        want = np.asarray(net.generate(jnp.asarray([p], jnp.int32), 6))
        assert toks == want[0, len(p):].tolist()
