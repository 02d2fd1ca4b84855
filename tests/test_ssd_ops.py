"""ops/ssd.py against the token-by-token recurrence (float32 on the CPU).

Tolerance 2e-5 absolute on values of order 1: the chunked form sums the same
products in another order (a block's quadratic form, then the carried state),
and float32 addition is not associative; nothing else separates them."""
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops import ssd

H, P, N, K, CD = 4, 8, 16, 4, 24
TOL = 2e-5


def _inputs(seed, t):
    r = np.random.default_rng(seed)
    return dict(
        x=jnp.asarray(r.normal(size=(t, H, P)), jnp.float32),
        dt=jnp.asarray(np.exp(r.uniform(np.log(1e-3), np.log(1e-1),
                                        (t, H))), jnp.float32),
        A=-jnp.asarray(r.uniform(1, 16, (H,)), jnp.float32),
        B=jnp.asarray(r.normal(size=(t, N)), jnp.float32),
        C=jnp.asarray(r.normal(size=(t, N)), jnp.float32),
        D=jnp.ones((H,), jnp.float32))


def _by_sequence(inp, lens, state):
    """Each sequence alone through the recurrence."""
    ys, finals, lo = [], [], 0
    for g, n in enumerate(lens):
        sl = slice(lo, lo + n)
        y, s = ssd.ssd_recurrence(inp["x"][sl], inp["dt"][sl], inp["A"],
                                  inp["B"][sl], inp["C"][sl], inp["D"],
                                  state[g])
        ys.append(y)
        finals.append(s)
        lo += n
    return jnp.concatenate(ys), jnp.stack(finals)


@pytest.mark.parametrize("chunk", [4, 7, 16, 64])
@pytest.mark.parametrize("lens,pad", [((23,), 0), ((5, 11, 1, 9), 6),
                                      ((16, 16), 0), ((3, 0, 20), 1)])
def test_chunked_scan_matches_the_recurrence(chunk, lens, pad):
    t = sum(lens) + pad
    g = len(lens)
    inp = _inputs(7, t)
    state = jnp.asarray(np.random.default_rng(3).normal(size=(g, H, P, N)),
                        jnp.float32)
    tok_seg = np.full((t,), g, np.int32)
    lo = 0
    for i, n in enumerate(lens):
        tok_seg[lo:lo + n] = i
        lo += n
    y, final = ssd.ssd_chunked(**inp, state=state,
                               tok_seg=jnp.asarray(tok_seg), chunk=chunk)
    want_y, want_final = _by_sequence(inp, lens, state)
    n_live = sum(lens)
    np.testing.assert_allclose(y[:n_live], want_y, atol=TOL, rtol=TOL)
    np.testing.assert_allclose(final, want_final, atol=TOL, rtol=TOL)
    # an empty sequence keeps its state bit for bit
    for i, n in enumerate(lens):
        if n == 0:
            np.testing.assert_array_equal(final[i], state[i])


def test_a_run_split_in_two_carries_its_state():
    inp = _inputs(11, 30)
    zero = jnp.zeros((1, H, P, N), jnp.float32)
    seg = jnp.zeros((30,), jnp.int32)
    y_all, s_all = ssd.ssd_chunked(**inp, state=zero, tok_seg=seg, chunk=8)
    cut = 13
    part = lambda sl: {k: (v[sl] if v.shape[0] == 30 else v)  # noqa: E731
                       for k, v in inp.items()}
    y1, s1 = ssd.ssd_chunked(**part(slice(0, cut)), state=zero,
                             tok_seg=seg[:cut], chunk=8)
    y2, s2 = ssd.ssd_chunked(**part(slice(cut, 30)), state=s1,
                             tok_seg=seg[cut:], chunk=8)
    np.testing.assert_allclose(jnp.concatenate([y1, y2]), y_all, atol=TOL,
                               rtol=TOL)
    np.testing.assert_allclose(s2, s_all, atol=TOL, rtol=TOL)


def test_step_is_the_recurrence_and_zero_dt_holds_a_row():
    inp = _inputs(5, 6)
    state = jnp.asarray(np.random.default_rng(1).normal(size=(6, H, P, N)),
                        jnp.float32)
    dt = inp["dt"].at[2].set(0.0)
    y, new = ssd.ssd_step(inp["x"], dt, inp["A"], inp["B"], inp["C"],
                          inp["D"], state)
    np.testing.assert_array_equal(new[2], state[2])
    for r in (0, 5):
        want_y, want_s = ssd.ssd_recurrence(
            inp["x"][r:r + 1], dt[r:r + 1], inp["A"], inp["B"][r:r + 1],
            inp["C"][r:r + 1], inp["D"], state[r])
        np.testing.assert_allclose(y[r], want_y[0], atol=TOL, rtol=TOL)
        np.testing.assert_allclose(new[r], want_s, atol=TOL, rtol=TOL)


def _conv_reference(x, w, b, tail):
    full = jnp.concatenate([tail, x])
    y = sum(full[k:k + x.shape[0]] * w[k] for k in range(K)) + b
    return y, full[-(K - 1):]


@pytest.mark.parametrize("lens,pad", [((9,), 0), ((1, 2, 7, 3), 3),
                                      ((2, 0, 5), 1)])
def test_conv_chunk_carries_each_sequences_tail(lens, pad):
    r = np.random.default_rng(2)
    t, g = sum(lens) + pad, len(lens)
    x = jnp.asarray(r.normal(size=(t, CD)), jnp.float32)
    w = jnp.asarray(r.normal(size=(K, CD)), jnp.float32)
    b = jnp.asarray(r.normal(size=(CD,)), jnp.float32)
    tail = jnp.asarray(r.normal(size=(g, K - 1, CD)), jnp.float32)
    tok_seg = np.full((t,), g, np.int32)
    lo = 0
    for i, n in enumerate(lens):
        tok_seg[lo:lo + n] = i
        lo += n
    y, new = ssd.causal_conv_chunk(x, w, b, tail, jnp.asarray(tok_seg))
    lo = 0
    for i, n in enumerate(lens):
        wy, wt = _conv_reference(x[lo:lo + n], w, b, tail[i])
        np.testing.assert_allclose(y[lo:lo + n], wy, atol=1e-6, rtol=1e-6)
        np.testing.assert_allclose(new[i], wt, atol=0, rtol=0)
        lo += n


def test_conv_step_is_a_chunk_of_one():
    r = np.random.default_rng(4)
    x = jnp.asarray(r.normal(size=(3, CD)), jnp.float32)
    w = jnp.asarray(r.normal(size=(K, CD)), jnp.float32)
    b = jnp.asarray(r.normal(size=(CD,)), jnp.float32)
    tail = jnp.asarray(r.normal(size=(3, K - 1, CD)), jnp.float32)
    y, new = ssd.causal_conv_step(x, w, b, tail)
    for i in range(3):
        wy, wt = _conv_reference(x[i:i + 1], w, b, tail[i])
        np.testing.assert_allclose(y[i], wy[0], atol=1e-6, rtol=1e-6)
        np.testing.assert_array_equal(new[i], wt)
