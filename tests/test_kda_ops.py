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
