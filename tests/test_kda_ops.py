"""ops/kda.py: the delta rule's step and chunk form against the definition
(``kda_recurrence``: a scan over the tokens of one sequence), float32 on the
CPU, at 2 heads of ``K`` 32 / ``V`` 16.

Tolerances: all three are float32 and the chunk form is the same mathematics
in another order of sums (a triangular inverse in the place of a token
loop): outputs of up to 0.4 and states of up to 1 agree to 1e-5 (4e-6 read
under a decay of e^-100 a token). A bfloat16 state or decay moves the
outputs by 50 times what the chunk form does (asserted below)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops import kda

H, K, V = 2, 32, 16
TOL = 1e-5


def inputs(t, seed=0, strength=1.0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    q = kda.l2norm(jax.random.normal(ks[0], (t, H, K))) * K ** -0.5
    k = kda.l2norm(jax.random.normal(ks[1], (t, H, K)))
    v = jax.random.normal(ks[2], (t, H, V))
    log_a = -strength * 0.3 * jnp.exp(jax.random.normal(ks[3], (t, H, K)))
    b = jax.nn.sigmoid(jax.random.normal(ks[4], (t, H)))
    return q, k, v, log_a, b


def packed(lengths, t, g):
    """``tok_seg`` [t] of sequences of ``lengths`` packed from row 0, the
    rest padding (``g``)."""
    seg, at = np.full(t, g), 0
    for i, n in enumerate(lengths):
        seg[at:at + n] = i
        at += n
    return jnp.asarray(seg)


def by_sequence(lengths):
    at = 0
    for i, n in enumerate(lengths):
        yield i, slice(at, at + n)
        at += n


def test_step_is_the_recurrences_one_token():
    q, k, v, log_a, b = inputs(5)
    s0 = jax.random.normal(jax.random.PRNGKey(7), (5, H, K, V))
    o, new = kda.kda_step(q, k, v, log_a, b, s0)
    for r in range(5):
        want_o, want_s = kda.kda_recurrence(
            *(x[r:r + 1] for x in (q, k, v, log_a, b)), s0[r])
        np.testing.assert_allclose(o[r], want_o[0], atol=TOL)
        np.testing.assert_allclose(new[r], want_s, atol=TOL)
    # by hand: S' = Diag(a) S; S = S' - b k (k^T S') + b k v^T; o = S^T q
    a = np.exp(np.asarray(log_a[0, 0]))[:, None] * np.asarray(s0[0, 0])
    kk, bb = np.asarray(k[0, 0]), float(b[0, 0])
    want = a - bb * np.outer(kk, kk @ a) + bb * np.outer(kk, v[0, 0])
    np.testing.assert_allclose(new[0, 0], want, atol=TOL)
    np.testing.assert_allclose(o[0, 0], want.T @ np.asarray(q[0, 0]),
                               atol=TOL)


def test_a_row_with_no_decay_and_no_write_keeps_its_state():
    q, k, v, log_a, b = inputs(3)
    s0 = jax.random.normal(jax.random.PRNGKey(1), (3, H, K, V))
    dead = jnp.asarray([False, True, False])
    _, new = kda.kda_step(q, k, v, jnp.where(dead[:, None, None], 0, log_a),
                          jnp.where(dead[:, None], 0, b), s0)
    assert np.array_equal(new[1], s0[1])
    assert not np.allclose(new[0], s0[0])


@pytest.mark.parametrize("lengths,t,chunk", [
    ((37, 50, 13), 112, 48),      # three sequences, 12 padded rows, 3 pieces
    ((64,), 64, 256),             # one sequence, one piece
    ((5, 1, 30), 37, 16),         # a run that is no multiple of a block
    ((16, 16), 32, 16)],          # boundaries on the blocks' edges
    ids=["three-and-padding", "one", "ragged-37", "on-the-edges"])
@pytest.mark.parametrize("strength", [1.0, 40.0], ids=["mild", "strong"])
def test_chunk_form_is_the_recurrence(lengths, t, chunk, strength,
                                      monkeypatch):
    """Several sequences in one run, each from its own carried state, a
    sequence absent from the run, padded rows, the run walked in pieces of
    ``chunk`` rows; ``strong``: ``log a`` down to -100 a token, where
    ``1 / e^{G_i}`` of a quotient form is infinite after a token."""
    monkeypatch.setattr(kda, "_PIECE", chunk)
    g = 4
    q, k, v, log_a, b = inputs(t, seed=len(lengths), strength=strength)
    s0 = jax.random.normal(jax.random.PRNGKey(9), (g, H, K, V))
    o, new = kda.kda_chunked(q, k, v, log_a, b, s0, packed(lengths, t, g))
    assert bool(jnp.isfinite(o[:sum(lengths)]).all())
    for i, rows in by_sequence(lengths):
        want_o, want_s = kda.kda_recurrence(
            *(x[rows] for x in (q, k, v, log_a, b)), s0[i])
        np.testing.assert_allclose(o[rows], want_o, atol=TOL)
        np.testing.assert_allclose(new[i], want_s, atol=TOL)
    for i in range(len(lengths), g):          # no row in the run: kept
        assert np.array_equal(new[i], s0[i])
    if strength > 1:
        total = jnp.cumsum(log_a, 0)
        assert not bool(jnp.isfinite(jnp.exp(-total[lengths[0] - 1])).all())


def test_gathered_form_carries_each_sequences_row_in_and_out_once():
    """Over a layer's whole state array: the rows of the run's sequences
    move, a ``fresh`` one starts from zeros, the scratch row takes what the
    absent entries of ``seg_rows`` write, every other row is untouched."""
    lengths, t, g, slots = (20, 12), 32, 4, 6
    q, k, v, log_a, b = inputs(t, seed=3)
    state = jax.random.normal(jax.random.PRNGKey(2), (slots + 1, H, K, V))
    seg_rows = jnp.asarray([4, 1, slots, slots])
    fresh = jnp.asarray([False, True, False, False])
    o, new = kda.kda_chunk_gathered(q, k, v, log_a, b, state,
                                    packed(lengths, t, g), seg_rows, fresh)
    for (i, rows), row, start in zip(by_sequence(lengths), (4, 1),
                                     (state[4], jnp.zeros((H, K, V)))):
        want_o, want_s = kda.kda_recurrence(
            *(x[rows] for x in (q, k, v, log_a, b)), start)
        np.testing.assert_allclose(o[rows], want_o, atol=TOL)
        np.testing.assert_allclose(new[row], want_s, atol=TOL)
    for row in (0, 2, 3, 5):                  # the neighbours
        assert np.array_equal(new[row], state[row])


def test_a_lower_precision_state_or_decay_is_no_chunk_forms_rounding():
    """What a bfloat16 state or a bfloat16 ``log a`` moves the outputs by
    is 50 times what separates the chunk form from the definition on the
    same rows: a comparison that passes the one cannot pass the other."""
    q, k, v, log_a, b = inputs(64, seed=5, strength=4.0)
    s0 = jnp.zeros((H, K, V))
    want, _ = kda.kda_recurrence(q, k, v, log_a, b, s0)
    got, _ = kda.kda_chunked(q, k, v, log_a, b, s0[None],
                             jnp.zeros((64,), jnp.int32))
    err = float(jnp.abs(got - want).max())
    assert err < TOL

    def rounded(x):
        return x.astype(jnp.bfloat16).astype(jnp.float32)

    decay, _ = kda.kda_recurrence(q, k, v, rounded(log_a), b, s0)

    def step(s, inp):
        o, s = kda.kda_step(*(x[None] for x in inp), s[None])
        return rounded(s[0]), o[0]

    _, state = jax.lax.scan(step, s0, (q, k, v, log_a, b))
    for moved in (decay, state):
        assert float(jnp.abs(moved - want).max()) > 50 * max(err, 1e-7)


def test_unit_lower_inverse_is_the_inverse():
    # entries as the rule's are: b <k_t, k_i> of unit keys, well under 1
    low = 0.2 * jnp.tril(jax.random.normal(jax.random.PRNGKey(0),
                                           (3, 64, 64)), -1)
    inv = kda._unit_lower_inverse(low)
    np.testing.assert_allclose(
        jnp.matmul(inv, jnp.eye(64) + low, precision="highest"),
        jnp.broadcast_to(jnp.eye(64), (3, 64, 64)), atol=2e-5)
    # all ones below the diagonal (a repeated key, b = 1, no decay): the
    # inverse is the difference operator, entries of 1 and -1, where a
    # product of powers of ``low`` would cancel numbers of 1e13
    ones = jnp.tril(jnp.ones((64, 64)), -1)
    want = jnp.eye(64) - jnp.eye(64, k=-1)
    np.testing.assert_allclose(kda._unit_lower_inverse(ones), want,
                               atol=1e-6)


# -- the chunk form's indexing against the parent's text, bit for bit ---------
# (PR 43 changed how the inverse takes its blocks out of ``L`` and no
# arithmetic: the form it replaced stays here as the oracle, under ``==``)

def _einsum_inverse(low):
    """``_unit_lower_inverse`` as it stood: the diagonal blocks of ``2s``
    rows taken by an einsum with a repeated index (a mask and a sum over
    the whole ``[T, T]``), their lower left ``[s, s]`` used."""
    t = low.shape[-1]
    lead = low.shape[:-2]
    inv = jnp.ones(lead + (t, 1, 1), jnp.float32)
    s = 1
    while s < t:
        n = t // (2 * s)
        pairs = jnp.einsum("...iaib->...iab",
                           low.reshape(lead + (n, 2 * s, n, 2 * s)))
        a, b = inv[..., 0::2, :, :], inv[..., 1::2, :, :]
        under = -jnp.matmul(b, jnp.matmul(pairs[..., s:, :s], a,
                                          precision=kda._HI),
                            precision=kda._HI)
        inv = jnp.concatenate(
            [jnp.concatenate([a, jnp.zeros_like(a)], -1),
             jnp.concatenate([under, b], -1)], -2)
        s *= 2
    return inv[..., 0, :, :]


def _parent_kda_block(q, k, v, log_a, b, oh, state):
    """``_kda_block`` as it stood, over the einsum form above."""
    hi = kda._HI
    t = q.shape[0]
    ohf = oh.astype(jnp.float32)
    same = jnp.matmul(ohf, ohf.T) > 0
    upto = jnp.tril(jnp.ones((t, t), bool))
    g = jnp.cumsum(log_a, axis=0)
    first = jnp.argmax(oh, axis=0)
    g_before = jnp.where((first > 0)[:, None, None],
                         g[jnp.maximum(first - 1, 0)], 0.0)
    last = t - 1 - jnp.argmax(oh[::-1], axis=0)
    total = g[last] - g_before
    g_seq = g - jnp.einsum("tg,ghk->thk", ohf, g_before, precision=hi)
    to_end = jnp.einsum("tg,ghk->thk", ohf, total, precision=hi) - g_seq
    prods = kda._decayed_products(jnp.stack([k, q]), k, g, same & upto)
    a_kk = jnp.where(jnp.eye(t, dtype=bool), 0.0, prods[0])
    a_qk = prods[1]
    bh = b.T[:, :, None]
    by_seq = ohf.T[:, :, None, None]
    grown = jnp.stack([k, q]) * jnp.exp(jnp.minimum(g_seq, 0.0))
    from_state = jnp.einsum("xgthk,ghkv->xhtv", grown[:, None] * by_seq,
                            state, precision=hi)
    rhs = bh * (v.transpose(1, 0, 2) - from_state[0])
    w = jnp.matmul(_einsum_inverse(bh * a_kk), rhs, precision=hi)
    o = from_state[1] + jnp.matmul(a_qk, w, precision=hi)
    left = k * jnp.exp(jnp.minimum(to_end, 0.0))
    new = jnp.exp(total)[..., None] * state + jnp.einsum(
        "gthk,htv->ghkv", left[None] * by_seq, w, precision=hi)
    present = jnp.any(oh, axis=0)
    return o.transpose(1, 0, 2), jnp.where(
        present[:, None, None, None], new, state)


@pytest.mark.parametrize("lead", [(3,), (2, 2)], ids=["heads", "two-axes"])
@pytest.mark.parametrize("t", [16, 32, 64, 128, 256])
def test_inverse_takes_its_blocks_by_index_and_gives_the_einsums_numbers(
        t, lead):
    low = 0.2 * jnp.tril(jax.random.normal(jax.random.PRNGKey(t),
                                           lead + (t, t)), -1)
    got = jax.jit(kda._unit_lower_inverse)(low)
    want = jax.jit(_einsum_inverse)(low)
    assert got.shape == want.shape == lead + (t, t)
    assert bool((got == want).all())
    # nothing of the indexing is a mask and a sum
    text = str(jax.make_jaxpr(kda._unit_lower_inverse)(low))
    assert "reduce_sum" not in text and "select_n" not in text


# (lengths, rows t, states g, seg_rows over 6 + 1 state rows, fresh)
_PINNED = {
    "one": ((64,), 64, 1, (3,), ()),
    "two-meet-inside-a-block": ((21, 43), 64, 2, (5, 0), ()),
    "eight": ((9, 30, 1, 17, 40, 5, 12, 14), 128, 8,
              (0, 1, 2, 3, 4, 5, 6, 6), ()),
    "a-fresh-one": ((20, 12), 32, 4, (4, 1, 6, 6), (1,)),
    "an-absent-slot": ((16, 16), 32, 4, (2, 6, 5, 6), ()),
    "padded-rows": ((37, 50, 13), 112, 4, (1, 4, 0, 6), (2,)),
    "no-power-of-two": ((5, 1, 30), 37, 4, (0, 3, 5, 6), ()),
    "two-pieces": ((200, 100), 300, 2, (2, 4), (0,)),
}


@pytest.mark.parametrize("strength", [1.0, 40.0], ids=["mild", "strong"])
@pytest.mark.parametrize("case", list(_PINNED))
def test_chunk_forms_return_the_parents_numbers_bit_for_bit(
        case, strength, monkeypatch):
    """``kda_chunked`` and ``kda_chunk_gathered`` against themselves over
    the parent's ``_kda_block``: every live row's ``o`` and every state
    row under ``==`` (a padded row's ``o`` means nothing)."""
    lengths, t, g, seg_rows, fresh = _PINNED[case]
    q, k, v, log_a, b = inputs(t, seed=len(lengths), strength=strength)
    state = jax.random.normal(jax.random.PRNGKey(11), (7, H, K, V))
    seg = packed(lengths, t, g)
    rows_of = jnp.asarray(seg_rows)
    is_fresh = jnp.zeros((g,), bool).at[jnp.asarray(fresh, int)].set(True)
    # an absent slot of ``kda_chunked`` carries a state of its own
    carried = jax.random.normal(jax.random.PRNGKey(12), (g, H, K, V))

    def both():
        # a new function a call: nothing traced over the other block
        return (jax.jit(lambda *a: kda.kda_chunked(*a))(
                    q, k, v, log_a, b, carried, seg),
                jax.jit(lambda *a: kda.kda_chunk_gathered(*a))(
                    q, k, v, log_a, b, state, seg, rows_of, is_fresh))

    got = both()
    monkeypatch.setattr(kda, "_kda_block", _parent_kda_block)
    want = both()
    live = sum(lengths)
    for (o, new), (want_o, want_new) in zip(got, want):
        assert bool(jnp.isfinite(o[:live]).all())
        assert bool((o[:live] == want_o[:live]).all())
        assert bool((new == want_new).all())


# -- ONE decay a head: log_a [.., H, 1], keys of 96 beside values of 192, ------
# -- write strengths over the whole of (0, 2) ----------------------------------

SK, SV = 96, 192


def scalar_inputs(t, seed=0, strength=1.0, heads=H):
    """As :func:`inputs` at ``K`` 96 / ``V`` 192, with ``log_a`` [t, H, 1]
    and ``b`` drawn over (0, 2)."""
    ks = jax.random.split(jax.random.PRNGKey(seed + 100), 5)
    q = kda.l2norm(jax.random.normal(ks[0], (t, heads, SK))) * SK ** -0.5
    k = kda.l2norm(jax.random.normal(ks[1], (t, heads, SK)))
    v = jax.random.normal(ks[2], (t, heads, SV))
    log_a = -strength * 0.3 * jnp.exp(jax.random.normal(ks[3],
                                                        (t, heads, 1)))
    b = jax.random.uniform(ks[4], (t, heads), minval=0.02, maxval=1.98)
    return q, k, v, log_a, b


def widened(log_a, k):
    return jnp.broadcast_to(log_a, k.shape)


def test_step_takes_one_decay_a_head_as_the_broadcast():
    q, k, v, log_a, b = scalar_inputs(5)
    s0 = jax.random.normal(jax.random.PRNGKey(7), (5, H, SK, SV))
    o, new = kda.kda_step(q, k, v, log_a, b, s0)
    want_o, want_new = kda.kda_step(q, k, v, widened(log_a, k), b, s0)
    assert bool((o == want_o).all()) and bool((new == want_new).all())
    assert float(b.max()) > 1.5 and float(b.min()) < 1.0
    for r in range(5):
        ref_o, ref_s = kda.kda_recurrence(
            *(x[r:r + 1] for x in (q, k, v, log_a, b)), s0[r])
        np.testing.assert_allclose(o[r], ref_o[0], atol=TOL)
        np.testing.assert_allclose(new[r], ref_s, atol=TOL)


@pytest.mark.parametrize("lengths,t", [
    ((16,), 16),                      # one block
    ((5, 1, 30), 37),                 # no multiple of a block, no power of 2
    ((37, 50, 13), 112),              # three sequences, 12 padded rows
    ((100, 156), 256),                # the engine's chunk, one piece
    ((300, 140, 60), 512)],           # two pieces, a sequence across them
    ids=["16", "37", "112", "256", "512"])
@pytest.mark.parametrize("strength", [1.0, 40.0], ids=["mild", "strong"])
def test_scalar_chunk_form_is_the_recurrence(lengths, t, strength):
    """The scalar-decay pair products through the shared solve, inverse and
    state products: several sequences a run, each from its own carried
    state, one absent, padded rows, ``b`` up to 2 (``(I + L)^-1`` grows
    with it), a state that is not square."""
    g = 4
    q, k, v, log_a, b = scalar_inputs(t, seed=len(lengths),
                                      strength=strength)
    s0 = jax.random.normal(jax.random.PRNGKey(9), (g, H, SK, SV))
    o, new = jax.jit(kda.kda_chunked)(q, k, v, log_a, b, s0,
                                      packed(lengths, t, g))
    assert o.shape == (t, H, SV) and new.shape == s0.shape
    assert bool(jnp.isfinite(o[:sum(lengths)]).all())
    for i, rows in by_sequence(lengths):
        want_o, want_s = kda.kda_recurrence(
            *(x[rows] for x in (q, k, v, log_a, b)), s0[i])
        # 1e-5 of the largest entry: a state of carried normals reaches 4
        # and a run of 300 tokens at strengths up to 2 sums 300 writes
        np.testing.assert_allclose(
            o[rows], want_o, atol=TOL * max(1.0, float(jnp.abs(want_o).max())))
        np.testing.assert_allclose(
            new[i], want_s, atol=TOL * max(1.0, float(jnp.abs(want_s).max())))
    for i in range(len(lengths), g):          # no row in the run: kept
        assert np.array_equal(new[i], s0[i])


@pytest.mark.parametrize("lengths,t", [((21, 43), 64), ((300, 100), 400)],
                         ids=["64", "400"])
def test_scalar_chunk_form_is_the_per_channel_form_fed_the_broadcast(
        lengths, t):
    """The oracle of the new form: today's per-channel code given the same
    decay in every channel computes the same numbers at another cost."""
    g = 2
    q, k, v, log_a, b = scalar_inputs(t, seed=2)
    s0 = jax.random.normal(jax.random.PRNGKey(3), (g, H, SK, SV))
    seg = packed(lengths, t, g)
    o, new = kda.kda_chunked(q, k, v, log_a, b, s0, seg)
    want_o, want_new = kda.kda_chunked(q, k, v, widened(log_a, k), b, s0,
                                       seg)
    np.testing.assert_allclose(o, want_o, atol=TOL)
    np.testing.assert_allclose(new, want_new, atol=TOL)


def test_scalar_form_builds_no_operand_a_pair_a_channel():
    """The cost, not the numbers: a decay a head forms its exponentials a
    PAIR (``[H, T, T]``); a decay a channel a pair AND a channel (``[nb,
    16, 16, H, K]``), and between blocks a second product through a
    reference row. The choice is by the decay's shape alone."""
    t = 64
    q, k, v, log_a, b = scalar_inputs(t)
    s0 = jnp.zeros((1, H, SK, SV))
    seg = jnp.zeros((t,), jnp.int32)
    one = str(jax.make_jaxpr(kda.kda_chunked)(q, k, v, log_a, b, s0, seg))
    each = str(jax.make_jaxpr(kda.kda_chunked)(
        q, k, v, widened(log_a, k), b, s0, seg))
    pair_and_channel = f"f32[{t // 16},16,16,{H},{SK}]"
    assert pair_and_channel in each and pair_and_channel not in one
    assert f"f32[{H},{t},{t}]" in one
    # exponentials: T T H where 16 T H K stand (and the state's)
    assert one.count(" exp ") < each.count(" exp ")


def test_gathered_form_with_one_decay_a_head():
    lengths, t, g, slots = (20, 12), 32, 4, 6
    q, k, v, log_a, b = scalar_inputs(t, seed=3)
    state = jax.random.normal(jax.random.PRNGKey(2), (slots + 1, H, SK, SV))
    seg_rows = jnp.asarray([4, 1, slots, slots])
    fresh = jnp.asarray([False, True, False, False])
    o, new = kda.kda_chunk_gathered(q, k, v, log_a, b, state,
                                    packed(lengths, t, g), seg_rows, fresh)
    for (i, rows), row, start in zip(by_sequence(lengths), (4, 1),
                                     (state[4], jnp.zeros((H, SK, SV)))):
        want_o, want_s = kda.kda_recurrence(
            *(x[rows] for x in (q, k, v, log_a, b)), start)
        np.testing.assert_allclose(o[rows], want_o, atol=TOL)
        np.testing.assert_allclose(new[row], want_s, atol=TOL)
    for row in (0, 2, 3, 5):                  # the neighbours
        assert np.array_equal(new[row], state[row])


@pytest.mark.parametrize("form", ["step", "chunk"])
def test_a_unit_key_written_twice_at_strength_two_is_a_reflection(form):
    """By hand: no decay (``a = 1``) and ``b = 2`` make ``I - b k k^T`` the
    reflection across ``k``'s hyperplane, so the same unit key written
    twice with the same value returns ``k^T S`` to where it was; at ``b =
    1`` (a projection: what every test drew before) the first write
    already sets ``k^T S = v`` and the second changes nothing. ``b`` in (1,
    2) overshoots and comes back part of the way."""
    kk = np.zeros((SK,), np.float32)
    kk[3] = 1.0
    s0 = np.asarray(jax.random.normal(jax.random.PRNGKey(5), (SK, SV)))
    val = np.asarray(jax.random.normal(jax.random.PRNGKey(6), (SV,)))
    before = kk @ s0

    def twice(strength):
        q = k = jnp.broadcast_to(jnp.asarray(kk), (2, 1, SK))
        v = jnp.broadcast_to(jnp.asarray(val), (2, 1, SV))
        log_a = jnp.zeros((2, 1, 1))
        b = jnp.full((2, 1), strength)
        state = jnp.asarray(s0)[None]
        if form == "chunk":
            o, new = kda.kda_chunked(q, k, v, log_a, b, state[None],
                                     jnp.zeros((2,), jnp.int32))
            return np.asarray(o[:, 0]), np.asarray(new[0, 0])
        o, new = kda.kda_recurrence(q, k, v, log_a, b, state)
        return np.asarray(o[:, 0]), np.asarray(new[0])

    o, new = twice(2.0)
    # after one write k^T S = 2 v - k^T S_0; after two, k^T S_0 again
    np.testing.assert_allclose(o[0], 2 * val - before, atol=TOL)
    np.testing.assert_allclose(o[1], before, atol=TOL)
    np.testing.assert_allclose(kk @ new, before, atol=TOL)
    np.testing.assert_allclose(new, s0, atol=TOL)     # the whole state
    o, new = twice(1.0)
    np.testing.assert_allclose(o[0], val, atol=TOL)
    np.testing.assert_allclose(o[1], val, atol=TOL)
    assert float(np.abs(kk @ new - before).max()) > 0.1


@pytest.mark.parametrize("form,digest", [("chunk", "92f43e905aa100ad"),
                                         ("step", "27d4f06f041026ef")])
def test_a_decay_a_channel_lowers_to_the_text_of_the_parent_of_pr_47(form,
                                                                     digest):
    """The per-channel path is chosen by the decay's shape and nothing of
    it moved when the scalar forms came: ``kda_chunk_gathered`` (256 rows,
    4 heads of 32, 8 sequences over 12 state rows) and ``kda_step`` lower to
    the text the commit before PR 47 lowered (digests read there,
    4160782)."""
    import hashlib
    f, sds = jnp.float32, jax.ShapeDtypeStruct
    t, h, k, g, s = 256, 4, 32, 8, 12
    if form == "chunk":
        text = jax.jit(kda.kda_chunk_gathered).lower(
            sds((t, h, k), f), sds((t, h, k), f), sds((t, h, k), f),
            sds((t, h, k), f), sds((t, h), f), sds((s, h, k, k), f),
            sds((t,), jnp.int32), sds((g,), jnp.int32),
            sds((g,), bool)).as_text()
    else:
        text = jax.jit(kda.kda_step).lower(
            sds((s, h, k), f), sds((s, h, k), f), sds((s, h, k), f),
            sds((s, h, k), f), sds((s, h), f), sds((s, h, k, k), f)).as_text()
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == digest


# -- the step as a kernel over a layer's whole state array -------------------

def step_inputs(rows, heads, dk, dv_live, dv, channel, seed=0):
    """A decode tick's rows at strong decays, ``b`` over (0, 2); values (and
    so the state) past ``dv_live`` are the stored padding: zeros."""
    ks = jax.random.split(jax.random.PRNGKey(seed + 200), 6)
    stored = jnp.arange(dv) < dv_live
    q = kda.l2norm(jax.random.normal(ks[0], (rows, heads, dk))) * dk ** -0.5
    k = kda.l2norm(jax.random.normal(ks[1], (rows, heads, dk)))
    v = jax.random.normal(ks[2], (rows, heads, dv)) * stored
    log_a = -3.0 * jnp.exp(jax.random.normal(
        ks[3], (rows, heads, dk if channel else 1)))
    b = jax.random.uniform(ks[4], (rows, heads), minval=0.02, maxval=1.98)
    state = jax.random.normal(ks[5], (rows + 2, heads, dk, dv)) * stored
    return (q, k, v, log_a, b), state


@pytest.mark.parametrize("heads,head_block", [(5, 2), (3, None)],
                         ids=["ragged_last_block", "one_block"])
@pytest.mark.parametrize("dk,dv_live,dv", [(16, 128, 128), (96, 192, 256)],
                         ids=["K=V-lanes", "96x192_stored_256"])
@pytest.mark.parametrize("channel", [True, False],
                         ids=["decay_a_channel", "one_decay_a_head"])
def test_step_kernel_is_kda_step_over_the_whole_array_in_place(
        channel, dk, dv_live, dv, heads, head_block):
    """``kda_step_kernel`` (interpret mode) against ``kda_step``: ``o`` and
    the live rows' new state to float32 tolerance, both decays, ``K != V``,
    a head count that leaves a ragged last block; the rows that are not
    live, the rows past the inputs and the scratch row bit for bit; a
    ``first`` row whose old state is NaN starts from zeros."""
    rows = 6
    x, state = step_inputs(rows, heads, dk, dv_live, dv, channel)
    live = jnp.asarray([True, False, True, True, False, True])
    first = jnp.asarray([False, False, True, False, True, False])
    state = state.at[2].set(jnp.nan)        # the first & live row's old tile
    assert float(x[4].max()) > 1.5 and float(jnp.exp(x[3]).min()) < 0.01
    o, new = kda.kda_step_kernel(*x, state, live, first,
                                 head_block=head_block)
    entering = jnp.where((first & live)[:, None, None, None], 0.0,
                         state[:rows])
    want_o, want_new = kda.kda_step(*x, entering)
    lv = np.asarray(live)
    assert np.isfinite(np.asarray(o)).all()
    np.testing.assert_allclose(o[lv], want_o[lv], atol=TOL)
    np.testing.assert_allclose(new[:rows][lv], want_new[lv], atol=TOL)
    assert np.array_equal(o[~lv], np.zeros_like(o[~lv]))
    held = np.append(~lv, [True, True])     # and the rows past the inputs
    assert np.array_equal(np.asarray(new[held]), np.asarray(state[held]))
    # the stored padding stays zeros
    assert not np.asarray(new[:rows][lv][..., dv_live:]).any()
    # the same row from zeros, by the definition
    ref_o, ref_s = kda.kda_recurrence(
        *(a[2:3] for a in x), jnp.zeros_like(state[2]))
    np.testing.assert_allclose(o[2], ref_o[0], atol=TOL)
    np.testing.assert_allclose(new[2], ref_s, atol=TOL)


@pytest.mark.parametrize("live", [(False,) * 4, (False, False, True, False)],
                         ids=["nothing_live", "one_live"])
def test_step_kernel_moves_no_row_that_is_not_live(live):
    """With nothing live the call returns the array as it was (the held
    tile copied onto itself); with one live row, that row alone moves."""
    x, state = step_inputs(4, 3, 16, 128, 128, True, seed=1)
    live = jnp.asarray(live)
    o, new = kda.kda_step_kernel(*x, state, live, jnp.zeros((4,), bool),
                                 head_block=2)
    moved = np.asarray((new != state).any(axis=(1, 2, 3)))
    assert np.array_equal(moved, np.append(np.asarray(live), [False, False]))
    assert not np.asarray(o[~np.asarray(live)]).any()


def test_step_kernel_refuses_a_key_size_that_is_no_multiple_of_8():
    x, state = step_inputs(2, 2, 12, 128, 128, True)
    with pytest.raises(ValueError, match="key size 12 is not a multiple"):
        kda.kda_step_kernel(*x, state, jnp.ones((2,), bool),
                            jnp.zeros((2,), bool))
    x, state = step_inputs(2, 2, 16, 128, 128, True)
    with pytest.raises(ValueError, match="a decay of 4 channels"):
        kda.kda_step_kernel(x[0], x[1], x[2], x[3][..., :4], x[4], state,
                            jnp.ones((2,), bool), jnp.zeros((2,), bool))


def test_step_kernel_sums_in_float32_not_in_a_bfloat16_pass():
    """The sums over ``K`` are float32 multiply-adds: against the step
    computed in float64 the kernel errs as ``kda_step`` at ``HIGHEST``
    does, and fifty times under a bfloat16 product's error."""
    x, state = step_inputs(4, 2, 96, 192, 256, False, seed=3)
    live, first = jnp.ones((4,), bool), jnp.zeros((4,), bool)
    o, new = kda.kda_step_kernel(*x, state, live, first)
    q, k, v, log_a, b = (np.asarray(a, np.float64) for a in x)
    dec = np.exp(log_a)[..., None] * np.asarray(state[:4], np.float64)
    w = b[..., None] * (v - np.einsum("rhk,rhkv->rhv", k, dec))
    want_new = dec + k[..., None] * w[..., None, :]
    want_o = np.einsum("rhk,rhkv->rhv", q, want_new)
    rounded = np.einsum(
        "rhk,rhkv->rhv", np.asarray(x[1].astype(jnp.bfloat16), np.float64),
        np.asarray(jnp.asarray(dec, jnp.float32).astype(jnp.bfloat16),
                   np.float64))
    bf16_err = np.abs(rounded - np.einsum("rhk,rhkv->rhv", k, dec)).max()
    err = max(np.abs(np.asarray(o) - want_o).max(),
              np.abs(np.asarray(new[:4]) - want_new).max())
    assert err < 2e-6 and bf16_err > 50 * err
