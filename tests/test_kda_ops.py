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
