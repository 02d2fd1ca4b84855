"""models/ouro.py against the plain reference
(benchmark/reference/ouro_looped.py: float32, the passes and layers written
out, a full causal attention a pass) on seeded weights, at a small size on
the CPU (3 layers x 3 passes, hidden 64, 4 heads of 16): the whole-sequence
forward, ``ragged_forward`` over hand-built pages, and the served path
through ``LLMEngine`` with mixed ticks.

Tolerances: float32 throughout, so what separates the program from the
reference is the order of float32 sums (paged attention against a dense
softmax, fused against split projections): logits of up to 0.6 agree to
TOL = 5e-6; they read 4e-7. The reference with bfloat16 operands moves them
by 1.4e-2 and with float8 by 0.25 (``test_a_lower_precision_misses_the_
tolerance`` reads both): computing in a lower precision would miss the
tolerance by three orders of magnitude and more."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from benchmark import weights_looped
from benchmark.reference import ouro_looped as ref
from paddle_tpu.inference.llm import CacheView, LLMEngine, RaggedRows
from paddle_tpu.models import OuroConfig, OuroForCausalLM
from paddle_tpu.models.gpt import GPTForCausalLM, gpt_config
from paddle_tpu.models.ouro import LoopUnsupported, exit_step
from paddle_tpu.observability import server as dbgsrv

TOL = 5e-6
TINY = dict(num_hidden_layers=3, hidden_size=64, num_attention_heads=4,
            num_key_value_heads=4, head_dim=16, intermediate_size=96,
            vocab_size=128, rms_norm_eps=1e-6, rope_theta=1e6,
            total_ut_steps=3, early_exit_threshold=1.0)
ENGINE = dict(page_size=8, num_pages=64, max_len=128, prefill_chunk=16,
              kv_dtype="f32")


def build(seed=5, **over):
    """``(net, params, dims)`` around the benchmark's seeded arrays."""
    model = dict(TINY, **over)
    d = weights_looped.dims_of(model)
    params = weights_looped.make(d, seed, jnp.float32)
    pt.seed(0)
    net = OuroForCausalLM(OuroConfig(**model, max_position_embeddings=256))
    net.eval()
    assert set(net.state_dict()) == set(params)
    net.set_state_dict(params)
    return net, params, d


def prompts_of(lengths, seed=0):
    r = np.random.default_rng(seed)
    return [list(map(int, r.integers(0, 128, n))) for n in lengths]


def served_gap(params, d, prompt, out):
    """The benchmark's measure: the widest gap by which a served token's
    logit lies below the reference's best, teacher-forced; the reference's
    exit steps of the served positions beside it."""
    seq = np.asarray([prompt + out], np.int32)
    n, m = len(prompt), len(out)
    served = np.zeros_like(seq)
    served[0, n - 1:n + m - 1] = out
    got = ref.served_gaps(params, seq, np.asarray([n - 1]), np.asarray([m]),
                          served, d)
    return float(np.max(np.asarray(got["gap"]))), \
        np.asarray(got["exit_step"])[0, n - 1:n + m - 1]


def paged(net, sequences, chunk, page_size=8, zero_layers=()):
    """``ragged_forward`` by hand: every sequence's tokens but the last
    through the pages in packed chunks of ``chunk`` rows, ``zero_layers`` of
    the pool wiped, then ONE decode row a sequence. ``(logits [B, V], exit
    step [B])`` of the decode rows."""
    layers, kvh, hd = net.kv_cache_spec()
    pages_per_seq = 8
    pool = jnp.zeros((layers, 1 + pages_per_seq * len(sequences), page_size,
                      kvh, hd), jnp.float32)
    cache = CacheView(pool, pool)
    tables = 1 + np.arange(pages_per_seq * len(sequences),
                           dtype=np.int32).reshape(len(sequences), -1)
    flat = [(b, p, tok) for b, seq in enumerate(sequences)
            for p, tok in enumerate(seq[:-1])]
    for i in range(0, len(flat), chunk):
        part = flat[i:i + chunk]
        pad = chunk - len(part)
        rows = RaggedRows(
            jnp.asarray([t for _, _, t in part] + [0] * pad, jnp.int32),
            jnp.asarray([p for _, p, _ in part] + [0] * pad, jnp.int32),
            jnp.asarray([p + 1 for _, p, _ in part] + [0] * pad, jnp.int32),
            jnp.asarray(np.concatenate(
                [tables[[b for b, _, _ in part]],
                 np.zeros((pad, pages_per_seq), np.int32)])), chunk)
        _, cache, _ = net.ragged_forward(rows, cache)
    for layer in zero_layers:
        cache = cache._replace(k_pages=cache.k_pages.at[layer].set(0),
                               v_pages=cache.v_pages.at[layer].set(0))
    last = jnp.asarray([len(s) - 1 for s in sequences], jnp.int32)
    rows = RaggedRows(jnp.asarray([s[-1] for s in sequences], jnp.int32),
                      last, last + 1, jnp.asarray(tables))
    hidden, _, aux = net.ragged_forward(rows, cache)
    return net.ragged_logits(hidden), aux


@pytest.fixture(scope="module")
def model():
    return build()


def test_whole_sequence_forward_matches_the_reference(model):
    net, params, d = model
    ids = jnp.asarray(np.random.default_rng(0).integers(0, 128, (2, 37)),
                      jnp.int32)
    want = ref.logits(params, ids, d)
    assert float(jnp.max(jnp.abs(want))) > 0.3
    np.testing.assert_allclose(net(ids), want, atol=TOL, rtol=TOL)


def test_a_lower_precision_misses_the_tolerance(model):
    _, params, d = model
    ids = jnp.asarray(np.random.default_rng(0).integers(0, 128, (1, 37)),
                      jnp.int32)
    want = ref.logits(params, ids, d)
    for quant in ("bf16", "fp8"):
        moved = float(jnp.max(jnp.abs(ref.logits(params, ids, d, quant)
                                      - want)))
        assert moved > 100 * TOL, (quant, moved)


def test_pages_in_chunks_then_a_decode_row_match_the_references_logits(
        model):
    """Two sequences of different lengths, chunks of 16 rows over pages of
    8: the longer prompt crosses pages and chunks, the two share a chunk.
    The decode rows' LOGITS against the reference's full forward at the
    last position."""
    net, params, d = model
    seqs = prompts_of((38, 11), seed=3)
    logits, aux = paged(net, seqs, chunk=16)
    for b, seq in enumerate(seqs):
        want = ref.logits(params, jnp.asarray([seq], jnp.int32), d)[0, -1]
        np.testing.assert_allclose(logits[b], want, atol=TOL, rtol=TOL)
    assert aux.tolist() == [2, 2]


def test_the_passes_cache_layers_are_not_aliased(model):
    """A decode row reads pass 1's K/V from cache layers 3..5 and nowhere
    else: wiped, the next token's logits move; at ``total_ut_steps`` 1 the
    pool has ``L`` cache layers and the program is one pass."""
    net, params, d = model
    seqs = prompts_of((21,), seed=4)
    sound, _ = paged(net, seqs, chunk=16)
    wiped, _ = paged(net, seqs, chunk=16, zero_layers=(3, 4, 5))
    assert float(jnp.max(jnp.abs(sound - wiped))) > 1e-3
    assert net.kv_cache_spec() == (9, 4, 16)
    once, params1, d1 = build(total_ut_steps=1)
    assert once.kv_cache_spec() == (3, 4, 16)
    want = ref.logits(params1, jnp.asarray(seqs, jnp.int32), d1)[0, -1]
    np.testing.assert_allclose(paged(once, seqs, chunk=16)[0][0], want,
                               atol=TOL, rtol=TOL)
    # and one pass is not three
    assert float(jnp.max(jnp.abs(want - sound[0]))) > 1e-3


def test_engine_mixed_ticks_hold_to_the_reference(model):
    """Prompts of different lengths that share chunks and join at different
    times (4 slots, 7 requests, chunk 16), then decode: every served token
    within TOL of the reference's best, token for token what ``generate``
    gives, and the exit steps the engine counted are the reference's."""
    net, params, d = model
    prompts = prompts_of((5, 23, 9, 40, 3, 17, 33))
    with LLMEngine(net, max_seqs=4, **ENGINE) as eng:
        futs = [eng.submit(p, max_new_tokens=10) for p in prompts[:5]]
        outs = [f.result(timeout=600) for f in futs]
        futs = [eng.submit(p, max_new_tokens=10) for p in prompts[5:]]
        outs += [f.result(timeout=600) for f in futs]
        assert "m" in eng.tick_history and "d" in eng.tick_history
        counted = eng.loop_exit_step_rows.copy()
        assert eng.n_tokens == 70
    want_steps = np.zeros(3, np.int64)
    for p, o in zip(prompts, outs):
        toks = list(o["output_ids"])
        assert len(toks) == 10 and not o["truncated"]
        gap, steps = served_gap(params, d, p, toks)
        assert gap <= TOL
        want_steps += np.bincount(steps, minlength=3)
        want = np.asarray(net.generate(jnp.asarray([p], jnp.int32), 10))
        assert toks == want[0, len(p):].tolist()
    assert counted.tolist() == want_steps.tolist() == [0, 0, 70]


@pytest.mark.parametrize("knobs", [dict(max_seqs=1),
                                   dict(decode_ticks_per_dispatch=4)],
                         ids=["one_slot", "slab"])
def test_the_other_tick_paths_serve_the_same_tokens(model, knobs):
    """``one_slot``: all prompts through ONE slot in turn: no decode row
    ever beside a prompt row, every pass's pages handed to the next
    sequence."""
    net, params, d = model
    prompts = prompts_of((19, 6, 27), seed=11)
    with LLMEngine(net, **{"max_seqs": 2, **ENGINE, **knobs}) as eng:
        outs = [f.result(timeout=600) for f in
                [eng.submit(p, max_new_tokens=9) for p in prompts]]
        assert eng.loop_exit_step_rows.tolist() == [0, 0, 27]
    for p, o in zip(prompts, outs):
        assert served_gap(params, d, p, list(o["output_ids"]))[0] <= TOL


def test_a_prefix_cache_hit_reuses_every_passes_pages(model):
    """The second request shares two whole pages with the first: they are
    taken from the cache (all nine cache layers of them: a page is one index
    into every layer), and it is served what an engine without a prefix
    cache serves."""
    net, params, d = model
    shared = prompts_of((16,), seed=7)[0]
    first, second = shared + [5, 9, 2], shared + [77, 1, 30, 8]
    with LLMEngine(net, max_seqs=2, **ENGINE) as eng:
        eng.submit(first, max_new_tokens=6).result(timeout=600)
        hit = eng.submit(second, max_new_tokens=6).result(timeout=600)
        assert eng.n_cached_tokens == 16
    with LLMEngine(net, max_seqs=2, prefix_cache=False, **ENGINE) as eng:
        plain = eng.submit(second, max_new_tokens=6).result(timeout=600)
        assert eng.n_cached_tokens == 0
    assert list(hit["output_ids"]) == list(plain["output_ids"])
    assert served_gap(params, d, second, list(hit["output_ids"]))[0] <= TOL


def test_what_the_loop_cannot_run_is_refused_by_name(model):
    with pytest.raises(LoopUnsupported) as e:
        OuroConfig(**dict(TINY, early_exit_threshold=0.5))
    assert e.value.mechanism == "early_exit"
    draft = GPTForCausalLM(gpt_config("gpt2-small", num_layers=1,
                                      hidden_size=32, num_heads=2,
                                      vocab_size=128))
    with pytest.raises(NotImplementedError, match="looped model"):
        LLMEngine(model[0], max_seqs=2, num_pages=16, max_len=64,
                  draft_net=draft)


def test_the_gates_exit_step_is_the_references():
    """At a threshold below 1 (which the program refuses to SERVE) the
    gate's arithmetic still has to be the reference's: the first pass whose
    cumulative exit probability reaches the threshold, else the last."""
    lambdas = jnp.asarray(np.random.default_rng(2).uniform(
        0.0, 0.6, (4, 1, 500)), jnp.float32)
    for threshold in (0.3, 0.5, 0.9, 1.0):
        want = ref.exit_step(list(lambdas), threshold)
        got = exit_step(lambdas, threshold)
        assert got.tolist() == want.tolist()
        assert threshold == 1.0 or len(set(want[0].tolist())) >= 2
    assert set(exit_step(lambdas, 1.0)[0].tolist()) == {3}


def test_a_program_holds_one_stacks_text_whatever_the_passes(model):
    """The passes are a loop in the compiled program: the decode tick of 3
    passes lowers to the ``dot_general`` of the tick of 1 pass (four a
    layer, the gate's, the head's, the gathered attention's; it read 20
    and 19, the one more in the gate's cumulative product), inside a
    ``while``. Unrolled, each further pass would add 12."""
    def lowered(net):
        with LLMEngine(net, max_seqs=2, **ENGINE) as eng:
            ints = np.zeros((2,), np.int32)
            return eng._decode_fn.lower(
                eng._params, eng._buffers, eng._tokens_dev,
                eng._stage_decode(ints, ints), eng.k_pages, eng.v_pages,
                eng._key).as_text()

    three, one = lowered(model[0]), lowered(build(total_ut_steps=1)[0])
    assert three.count("dot_general") < one.count("dot_general") + 4
    assert "stablehlo.while" in three


def test_the_loop_is_on_the_spans_the_status_page_and_the_metrics(model):
    """What the per-layer metrics read: ``loop_steps`` / ``kv_cache_layers``
    on every ``llm.issue.*`` phase (the passes a row runs) and on
    ``llm.drain.emit`` (the passes its delivered tokens ran), the two loop
    counters, and a page priced at all nine cache layers."""
    from paddle_tpu.observability import metrics as obs
    from paddle_tpu.observability import tracing
    net, _, _ = model
    was = tracing.enabled()
    tracing.enable()
    tracing.clear()
    reg = obs.default_registry()

    def steps_total():
        fam = reg.get("llm_loop_steps_total")
        return 0.0 if fam is None else float(fam.value)

    before = steps_total()
    try:
        with LLMEngine(net, max_seqs=2, **ENGINE) as eng:
            for p in prompts_of((20, 7), seed=6):
                eng.submit(p, max_new_tokens=5).result(timeout=600)
            status = dbgsrv._collect_status()[eng._status_name]
            page = 2 * 9 * 8 * 4 * 16 * 4        # K and V, f32
            assert eng._page_bytes == status["page_bytes"] == page
        spans = tracing.finished_spans()
    finally:
        tracing.clear()
        if not was:
            tracing.disable()
    assert status["kv_cache_layers"] == 9
    assert status["loop"] == {"total_ut_steps": 3,
                              "exit_step_rows": [0, 0, 10]}
    assert steps_total() - before == 30
    issues = [s for s in spans if s["name"].startswith("llm.issue.")]
    drains = [s for s in spans if s["name"] == "llm.drain.emit"]
    assert issues and all(
        s["attrs"]["loop_steps"] == 3
        and s["attrs"]["kv_cache_layers"] == 9 for s in issues)
    assert sum(s["attrs"]["loop_steps"] for s in drains) == 30
    assert sum(s["attrs"]["tokens"] for s in drains) == 10


def test_the_issue_marks_leave_the_loops_attrs_where_the_parent_wrote_them(
        model, issue_phases):
    """ISSUE 37: ``packed`` / ``staged`` / ``launched`` / ``booked`` on every
    dispatch, and ``loop_steps`` with the page counts (stamped at the
    phase's end now) equal to the same run's on the parent's ordering."""
    from paddle_tpu.observability import tracing
    net, _, _ = model
    tracing.enable()
    with LLMEngine(net, max_seqs=2, **ENGINE) as eng:
        issue_phases.serve(eng, list(zip(prompts_of((20, 7, 5), seed=6),
                                         (5, 4, 6))))
    spans = tracing.finished_spans()
    issue_phases.check_marks(spans)
    assert all(s["attrs"]["loop_steps"] == 3
               for s in issue_phases.launched(spans))
    assert issue_phases.digest(spans) == "792898c9ebf0d505"
