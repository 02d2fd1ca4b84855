"""models/laguna.py against the plain reference
(benchmark/reference/laguna_swa.py: float32, a whole masked attention a
layer, no pages) on seeded weights, at a small size on the CPU (5 layers:
full, three sliding, full; window 24; 4 heads on a full layer and 6 on a
sliding one over 2 K/V heads of 16; a dense layer then 8 experts, 2 a token):
the whole-sequence forward, and the served path through ``LLMEngine``:
prompts in chunks through BOTH cache groups, then decode past the window
with the window group's pages released behind it.

Tolerances: float32 throughout, so what separates the program from the
reference is the order of float32 sums: logits of up to 0.5 agree to
TOL = 5e-6."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from benchmark import weights_swa
from benchmark.reference import laguna_swa as ref
from paddle_tpu.inference import page_pool
from paddle_tpu.inference.llm import CacheGroupUnsupported, LLMEngine
from paddle_tpu.models import LagunaConfig, LagunaForCausalLM
from paddle_tpu.models.gpt import GPTForCausalLM, gpt_config
from paddle_tpu.observability import server as dbgsrv
from paddle_tpu.ops import rotary

TOL = 5e-6
WINDOW = 24
TINY = dict(
    vocab_size=128, hidden_size=64, intermediate_size=96,
    num_hidden_layers=5, num_key_value_heads=2, head_dim=16,
    rms_norm_eps=1e-6, num_experts=8, num_experts_per_tok=2,
    moe_intermediate_size=32, shared_expert_intermediate_size=32,
    moe_routed_scaling_factor=2.5, mlp_only_layers=[0],
    sliding_window=WINDOW,
    layer_types=["full_attention"] + ["sliding_attention"] * 3
    + ["full_attention"],
    num_attention_heads_per_layer=[4, 6, 6, 6, 4],
    rope_parameters={
        "full_attention": {
            "rope_theta": 500000, "rope_type": "yarn", "factor": 128,
            "original_max_position_embeddings": 8192, "beta_slow": 1,
            "beta_fast": 32, "attention_factor": 1.4852030263919618,
            "partial_rotary_factor": 0.5},
        "sliding_attention": {"rope_type": "default", "rope_theta": 10000,
                              "partial_rotary_factor": 1}})
ENGINE = dict(page_size=8, num_pages=64, max_len=128, prefill_chunk=16,
              kv_dtype="f32")
RING = -(-(WINDOW + 16) // 8) + 1


def build(seed=5, **over):
    """``(net, params, dims)`` around the benchmark's seeded arrays."""
    model = dict(TINY, **over)
    d = weights_swa.dims_of(model)
    params = weights_swa.make(d, seed, jnp.float32)
    pt.seed(0)
    net = LagunaForCausalLM(LagunaConfig(
        **model, max_position_embeddings=256))
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
    """57 positions: more than two windows, so the sliding mask cuts."""
    net, params, d = model
    ids = jnp.asarray(np.random.default_rng(0).integers(0, 128, (2, 57)),
                      jnp.int32)
    want = ref.logits(params, ids, d)
    assert float(jnp.max(jnp.abs(want))) > 0.3
    np.testing.assert_allclose(net(ids), want, atol=TOL, rtol=TOL)
    # the window matters: a model that saw everything answers otherwise
    wide, _, _ = build(sliding_window=256)
    assert float(jnp.max(jnp.abs(wide(ids) - want))) > 1e-3


def test_a_lower_precision_misses_the_tolerance(model):
    _, params, d = model
    ids = jnp.asarray(np.random.default_rng(0).integers(0, 128, (1, 37)),
                      jnp.int32)
    want = ref.logits(params, ids, d)
    for quant in ("bf16", "fp8"):
        moved = float(jnp.max(jnp.abs(ref.logits(params, ids, d, quant)
                                      - want)))
        assert moved > 100 * TOL, (quant, moved)


def test_two_cache_groups_with_their_own_lifetimes(model):
    net, _, _ = model
    groups = net.kv_cache_spec()
    assert [(g.name, g.layers, g.kv_heads, g.head_dim, g.window)
            for g in groups] == [("full", 2, 2, 16, None),
                                 ("window", 3, 2, 16, WINDOW)]
    assert net.moe_aux_spec() == (4, 8)


@pytest.mark.parametrize("knobs", [dict(), dict(max_seqs=1),
                                   dict(attention_impl="pallas")],
                         ids=["mixed_ticks", "one_slot", "kernel"])
def test_engine_holds_to_the_reference_past_the_window(model, knobs):
    """Prompts shorter and longer than window + chunk that share chunks and
    join at different times (3 slots, 5 requests), then 40 tokens of decode:
    every sequence leaves the window behind. Every served token within TOL
    of the reference's best and what ``generate`` gives; the window group
    never holds more than its ring a slot, and released pages are POISONED
    as they go back to the free list, so a read of one would show.
    ``one_slot``: all five through ONE slot in turn, the window's ring handed
    from each sequence to the next."""
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
            futs = [eng.submit(p, max_new_tokens=40) for p in prompts[:4]]
            outs = [f.result(timeout=900) for f in futs]
            outs.append(eng.submit(prompts[4], max_new_tokens=40)
                        .result(timeout=900))
            full, window = eng._pool.groups
            assert window.ring == RING and full.ring is None
            assert window.n_released > 0 and full.n_released == 0
            assert peak["window"] <= RING
            assert peak["full"] == -(-(70 + 40) // 8)
            # the slots drained: every page is back on its free list
            assert len(full.free) == full.num_pages - 1
            assert len(window.free) == window.num_pages - 1
            assert window.num_pages == eng.max_seqs * RING + 1
            assert eng.moe_rows_by_expert.shape == (4, 8)
    for p, o in zip(prompts, outs):
        toks = list(o["output_ids"])
        assert len(toks) == 40 and not o["truncated"]
        assert served_gap(params, d, p, toks) <= TOL
        want = np.asarray(net.generate(jnp.asarray([p], jnp.int32), 40))
        assert toks == want[0, len(p):].tolist()


def test_engine_serves_a_share_of_the_experts(model):
    """Experts 2-5 of 8 held: the engine against the reference's same
    share, and the counters say what fell on the held ones."""
    net, params, d = build(experts_held=(2, 4))
    prompts = prompts_of((33, 12), seed=2)
    with LLMEngine(net, max_seqs=2, **ENGINE) as eng:
        outs = [f.result(timeout=600) for f in
                [eng.submit(p, max_new_tokens=12) for p in prompts]]
        assert eng.moe_rows_by_expert.shape == (4, 4)
        assert 0 < eng.n_moe_pairs_held < eng.n_moe_pairs
    for p, o in zip(prompts, outs):
        assert served_gap(params, d, p, list(o["output_ids"])) <= TOL


def test_modes_that_assume_one_lifetime_are_refused_by_name(model):
    net, _, _ = model
    pt.seed(0)
    draft = GPTForCausalLM(gpt_config("gpt2-small", num_layers=1,
                                      hidden_size=32, num_heads=2,
                                      vocab_size=128))
    for knobs, mechanism in ((dict(draft_net=draft), "speculative_verify"),
                             (dict(decode_ticks_per_dispatch=2),
                              "fused_slab")):
        with pytest.raises(CacheGroupUnsupported) as e:
            LLMEngine(net, max_seqs=2, **ENGINE, **knobs)
        assert e.value.mechanism == mechanism
    with LLMEngine(net, max_seqs=2, **ENGINE, prefix_cache=True) as eng:
        assert eng._cache is None
        for call in (lambda: eng.export_pages([]),
                     lambda: eng.import_pages({})):
            with pytest.raises(CacheGroupUnsupported) as e:
                call()
            assert e.value.mechanism == "kv_page_migration"
        status = dbgsrv._collect_status()[eng._status_name]
        assert [(g["name"], g["layers"], g["window"], g["ring_pages"])
                for g in status["cache_groups"]] == [
            ("full", 2, None, None), ("window", 3, WINDOW, RING)]
        assert set(status["cache_groups_unsupported"]) == {
            "prefix_reuse", "kv_page_migration", "speculative_verify",
            "fused_slab"}
        assert status["prefix_cache"]["enabled"] is False


def test_the_issue_phases_say_what_each_group_read_and_holds(model):
    """While tracing: ``kv_pages_read`` / ``kv_pages_live`` are sums over
    the groups, ``kv_groups`` has them by group with the bytes held, and a
    long sequence's window group reads and holds its window only."""
    from paddle_tpu.observability import tracing
    net, _, _ = model
    tracing.enable()
    try:
        tracing.clear()
        with LLMEngine(net, max_seqs=2, **ENGINE,
                       attention_impl="pallas") as eng:
            eng.submit(prompts_of((70,))[0], max_new_tokens=30) \
                .result(timeout=600)
            page_bytes = {g.name: g.page_bytes for g in eng._pool.groups}
        spans = [s for s in tracing.finished_spans()
                 if s["name"] == "llm.issue.decode"]
    finally:
        tracing.disable()
    last = spans[-1]["attrs"]
    groups = last["kv_groups"]
    assert last["kv_pages_read"] == sum(g["read"] for g in groups.values())
    assert last["kv_pages_live"] == sum(g["live"] for g in groups.values())
    assert groups["full"]["read"] == groups["full"]["live"] == \
        -(-last["context_tokens"] // 8)
    assert groups["window"]["live"] <= WINDOW // 8 + 1
    assert groups["window"]["bytes_held"] \
        <= RING * page_bytes["window"]
    assert groups["full"]["bytes_held"] \
        == groups["full"]["live"] * page_bytes["full"]
    assert sum(s["attrs"]["window_pages_released"] for s in spans) > 0


def test_the_issue_marks_leave_the_groups_attrs_where_the_parent_wrote_them(
        model, issue_phases):
    """ISSUE 37: ``packed`` / ``staged`` / ``launched`` / ``booked`` on every
    dispatch, and ``kv_groups`` with the sums over it (stamped at the
    phase's end now, after the window's pages were released, as before)
    equal to the same run's on the parent's ordering. (PR 42 moved the
    digest on purpose, from ``0afefd0483cde46f``: every group of
    ``kv_groups`` gained ``k_row_bytes`` / ``v_row_bytes``, what a token
    stores of K and of V; nothing else of the attrs moved.)"""
    from paddle_tpu.observability import tracing
    net, _, _ = model
    tracing.enable()
    with LLMEngine(net, max_seqs=2, **ENGINE,
                   attention_impl="pallas") as eng:
        issue_phases.serve(eng, list(zip(prompts_of((70, 20, 9)),
                                         (30, 8, 5))))
    spans = tracing.finished_spans()
    issue_phases.check_marks(spans)
    launched = issue_phases.launched(spans)
    assert sum(s["attrs"]["window_pages_released"] for s in launched) > 0
    assert issue_phases.digest(spans) == "13f0c1e81f095ef9"


def test_two_groups_tables_cross_in_the_one_transfer_and_stay_as_sent(
        model, issue_phases):
    """ISSUE 44, a pool of two groups (six host arrays before): ONE transfer
    a decode dispatch, both block tables inside it, 15 on a mixed one. And
    the hazard ``PagePool.device_tables`` documents: the window group's
    table is rewritten right after every launch (pages released behind the
    window), by the end of the run every sequence's rows are zero again,
    and each staged vector still reads what it read when it was launched,
    its tables the host tables of that moment."""
    from paddle_tpu.observability import tracing
    net, _, _ = model
    tracing.enable()
    with LLMEngine(net, max_seqs=2, **ENGINE) as eng, \
            issue_phases.decode_staging(eng) as seen:
        issue_phases.serve(eng, list(zip(prompts_of((70, 20, 9)),
                                         (30, 8, 5))))
        layout, tables = eng._decode_layout, eng._pool.host_tables()
        assert [t.shape for t in tables] == [(2, eng.pages_per_seq)] * 2
        assert not any(t.any() for t in tables)     # every slot freed
    spans = tracing.finished_spans()
    assert issue_phases.transfers(spans) == {"llm.issue.decode": {1},
                                             "llm.issue.mixed": {15}}
    assert sum(s["attrs"]["window_pages_released"]
               for s in issue_phases.launched(spans)) > 0
    assert len(seen) >= 20 and {n for n, _, _ in seen} == {1}
    assert layout.size == 4 * 2 + 2 * 2 * eng.pages_per_seq
    for _, staged, at_launch in seen:
        assert np.array_equal(np.asarray(staged), at_launch)
        _, lens, full, window, _, _ = jax.jit(layout.unpack)(staged)
        live = np.asarray(lens) > 0
        # a live row holds a page in both groups, the window group fewer
        assert live.any() and np.asarray(full)[live].any(axis=1).all()
        assert (np.count_nonzero(np.asarray(window)[live], axis=1)
                <= RING).all()
    assert max(np.count_nonzero(at[2 * 2:2 * 2 + 2 * eng.pages_per_seq])
               for _, _, at in seen) > RING


# -- the rotary schemes against a direct transcription ----------------------

def test_yarn_inverse_frequencies_are_the_formulas():
    import math
    rot, theta, factor, orig = 64, 5e5, 128.0, 8192
    low = math.floor(rot * math.log(orig / (32 * 2 * math.pi))
                     / (2 * math.log(theta)))
    high = math.ceil(rot * math.log(orig / (1 * 2 * math.pi))
                     / (2 * math.log(theta)))
    low, high = max(low, 0), min(high, rot - 1)
    want = []
    for i in range(rot // 2):
        f = theta ** (-2 * i / rot)
        m = 1 - min(max((i - low) / (high - low), 0.0), 1.0)
        want.append((f / factor) * (1 - m) + f * m)
    got = rotary.yarn_inv_freq(rot, theta, factor, orig, 32, 1)
    np.testing.assert_allclose(got, want, rtol=1e-6)
    assert 0 < low < high < rot // 2     # all three regimes occur
    assert got[0] == pytest.approx(1.0) and \
        got[-1] == pytest.approx(theta ** (-(rot - 2) / rot) / factor,
                                 rel=1e-6)


def test_half_of_each_head_rotates_and_the_rest_passes():
    r = np.random.default_rng(0)
    q = jnp.asarray(r.normal(size=(1, 5, 3, 16)), jnp.float32)
    k = jnp.asarray(r.normal(size=(1, 5, 2, 16)), jnp.float32)
    pos = jnp.asarray([0, 3, 9, 100, 4000])
    inv = rotary.yarn_inv_freq(8, 5e5, 128.0, 8192)
    factor = 1.4852030263919618
    cos, sin = rotary.rope_at(pos, 8, inv_freq=inv, attention_factor=factor)
    q2, k2 = rotary.apply_partial_rotary(q, k, cos, sin)
    np.testing.assert_array_equal(q2[..., 8:], q[..., 8:])
    np.testing.assert_array_equal(k2[..., 8:], k[..., 8:])
    for x, y in ((q, q2), (k, k2)):
        for t, p in enumerate(np.asarray(pos)):
            for i in range(4):
                a = float(p) * float(inv[i])
                c, s = np.cos(a) * factor, np.sin(a) * factor
                x1, x2 = np.asarray(x[0, t, :, i]), \
                    np.asarray(x[0, t, :, i + 4])
                np.testing.assert_allclose(y[0, t, :, i], x1 * c - x2 * s,
                                           rtol=2e-5, atol=2e-5)
                np.testing.assert_allclose(y[0, t, :, i + 4],
                                           x2 * c + x1 * s,
                                           rtol=2e-5, atol=2e-5)
    # tables as wide as the head: the plain whole-head rotation
    cos, sin = rotary.rope_at(pos, 16, 1e4)
    whole = rotary.apply_partial_rotary(q, k, cos, sin)
    plain = rotary.apply_rotary_pos_emb(q, k, cos, sin)
    np.testing.assert_array_equal(whole[0], plain[0])
