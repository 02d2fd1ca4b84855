"""ops/ssd.py ``ssd_step_kernel`` (the Pallas state step, through the
interpreter on the CPU) against ``ssd_step``, its oracle: the same float32
recurrence over the live rows, zeros under a ``first`` row whatever lay
there, and not a byte moved in a row that is not live or in the scratch row.

What the interpreter cannot show (that the TPU's pipeline neither fetches
nor writes a tile a step does not name anew) is held on the chip by
``chip_smoke.py``'s kernel phase, which compares whole state arrays."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops import ssd

ROWS, SLOTS, H, P, N = 6, 7, 8, 8, 16
TOL = 1e-5

PATTERNS = {
    #                 live                 first
    "all_live":      ((1, 1, 1, 1, 1, 1), (0, 0, 0, 0, 0, 0)),
    "none_live":     ((0, 0, 0, 0, 0, 0), (0, 1, 0, 0, 1, 0)),
    "leading_idle":  ((0, 0, 1, 1, 0, 1), (0, 0, 0, 1, 0, 0)),
    "trailing_idle": ((1, 1, 0, 1, 0, 0), (1, 0, 0, 0, 1, 0)),
    "all_first":     ((1, 0, 1, 1, 0, 1), (1, 1, 1, 1, 1, 1)),
    "first_then_read": ((0, 1, 1, 0, 1, 1), (0, 1, 1, 0, 0, 0)),
}


def inputs(seed=0, dtype=jnp.float32, rows=ROWS):
    k = jax.random.split(jax.random.PRNGKey(seed), 7)
    return dict(
        x=jax.random.normal(k[0], (rows, H, P), jnp.float32).astype(dtype),
        dt=jax.nn.softplus(jax.random.normal(k[1], (rows, H))),
        A=-jnp.exp(jax.random.normal(k[2], (H,))),
        B=jax.random.normal(k[3], (rows, N)),
        C=jax.random.normal(k[4], (rows, N)),
        D=jax.random.normal(k[5], (H,)),
        state=jax.random.normal(k[6], (SLOTS, H, P, N)))


def flags(pattern):
    live, first = PATTERNS[pattern]
    return jnp.asarray(live, bool), jnp.asarray(first, bool)


def plain(inp, state, live, first):
    """What ``ragged_forward`` does off the TPU, on the live rows."""
    rows = inp["x"].shape[0]
    y, new = ssd.ssd_step(
        inp["x"], jnp.where(live[:, None], inp["dt"], 0.0), inp["A"],
        inp["B"], inp["C"], inp["D"],
        jnp.where((first & live)[:, None, None, None], 0.0, state[:rows]))
    return y, state.at[:rows].set(new)


def kernel(inp, state, live, first, **kw):
    return ssd.ssd_step_kernel(inp["x"], inp["dt"], inp["A"], inp["B"],
                               inp["C"], inp["D"], state, live, first,
                               interpret=True, **kw)


def poisoned(state, live, first):
    """NaN wherever the kernel must neither read nor write: the rows that
    are not live, the scratch row, and what lies under a ``first`` row."""
    rows = live.shape[0]
    untouched = jnp.concatenate(
        [~live, jnp.ones((state.shape[0] - rows,), bool)])
    unread = jnp.concatenate(
        [live & first, jnp.zeros((state.shape[0] - rows,), bool)])
    return jnp.where((untouched | unread)[:, None, None, None], jnp.nan,
                     state), untouched


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("head_block", [8, 4, 3, 1, None],
                         ids=["hb8_all", "hb4_divides", "hb3_ragged",
                              "hb1", "hb_from_shapes"])
def test_kernel_matches_ssd_step(dtype, head_block):
    inp = inputs(1, dtype)
    live, first = flags("trailing_idle")
    want_y, want_s = plain(inp, inp["state"], live, first)
    y, new = kernel(inp, inp["state"], live, first, head_block=head_block)
    assert y.dtype == jnp.float32 and new.dtype == jnp.float32
    np.testing.assert_allclose(y[live], want_y[live], atol=TOL, rtol=TOL)
    np.testing.assert_allclose(new, want_s, atol=TOL, rtol=TOL)


@pytest.mark.parametrize("pattern", list(PATTERNS))
@pytest.mark.parametrize("head_block", [8, 3], ids=["hb8", "hb3_ragged"])
def test_rows_start_from_zeros_or_hold_their_bytes(pattern, head_block):
    """A ``first`` row's old state is never read (NaN under it, a finite
    step from zeros out of it); a row that is not live and the scratch row
    keep their bytes exactly, NaN payloads included, and give the skip
    term alone as ``y``."""
    inp = inputs(2)
    live, first = flags(pattern)
    state, untouched = poisoned(inp["state"], live, first)
    want_y, want_s = plain(inp, jnp.nan_to_num(state), live, first)
    y, new = kernel(inp, state, live, first, head_block=head_block)
    assert np.array_equal(np.asarray(new[untouched]).view(np.uint32),
                          np.asarray(state[untouched]).view(np.uint32))
    moved = ~untouched
    assert np.isfinite(np.asarray(new[moved])).all()
    np.testing.assert_allclose(new[moved], want_s[moved], atol=TOL, rtol=TOL)
    np.testing.assert_allclose(y[live], want_y[live], atol=TOL, rtol=TOL)
    skip = inp["D"][None, :, None] * inp["x"]
    np.testing.assert_array_equal(y[~live], skip[~live])


@pytest.mark.parametrize("head_block", [8, 3], ids=["hb8", "hb3_ragged"])
def test_inside_a_scan_the_state_is_the_carry(head_block):
    """The slab: ticks of one program, the state carried from tick to tick
    under ``lax.scan``, rows going idle and starting anew between them."""
    ticks = 4
    inp = inputs(3, rows=ROWS)
    xs = jax.random.normal(jax.random.PRNGKey(9), (ticks, ROWS, H, P))
    lives = jnp.asarray([PATTERNS[p][0] for p in
                         ("all_live", "leading_idle", "trailing_idle",
                          "first_then_read")], bool)
    firsts = jnp.asarray([PATTERNS[p][1] for p in
                          ("all_live", "leading_idle", "trailing_idle",
                           "first_then_read")], bool)

    def run(step):
        def tick(state, t):
            x, live, first = t
            y, state = step(dict(inp, x=x), state, live, first)
            return state, jnp.where(live[:, None, None], y, 0.0)
        return jax.jit(lambda s: jax.lax.scan(tick, s,
                                              (xs, lives, firsts)))

    want_s, want_y = run(plain)(inp["state"])
    got_s, got_y = run(lambda *a: kernel(*a, head_block=head_block))(
        inp["state"])
    np.testing.assert_allclose(got_y, want_y, atol=TOL, rtol=TOL)
    np.testing.assert_allclose(got_s, want_s, atol=TOL, rtol=TOL)


def test_the_state_is_its_own_output():
    """``input_output_aliases`` names the state (operand 10 of the call:
    six prefetched index vectors and four small operands come first) as
    output 1, and a program that donates it gets the same answer and
    takes the buffer."""
    inp = inputs(4)
    live, first = flags("leading_idle")
    fn = jax.jit(lambda state: kernel(inp, state, live, first),
                 donate_argnums=(0,))
    calls = [e for e in jax.make_jaxpr(
        lambda s: ssd._ssd_step_call.__wrapped__(
            inp["x"], inp["dt"], inp["A"], inp["B"], inp["C"], inp["D"], s,
            live, first, head_block=4, interpret=True))(
                inp["state"]).jaxpr.eqns if e.primitive.name == "pallas_call"]
    assert len(calls) == 1
    assert tuple(calls[0].params["input_output_aliases"]) == ((10, 1),)
    assert calls[0].invars[10].aval.shape == inp["state"].shape
    assert calls[0].params["name"] == "ssd_step"    # the trace's name
    want_y, want_s = plain(inp, inp["state"], live, first)
    donated = jnp.array(inp["state"])
    y, new = fn(donated)
    assert donated.is_deleted()
    np.testing.assert_allclose(new, want_s, atol=TOL, rtol=TOL)
    np.testing.assert_allclose(y[live], want_y[live], atol=TOL, rtol=TOL)


def test_one_trace_serves_every_layer(monkeypatch):
    """An engine program calls the kernel once a state-space layer with
    the same shapes: the jitted call is traced (and so lowered) once for
    all of them."""
    inp = inputs(5)
    live, first = flags("all_live")
    traced = []
    real = ssd.pl.pallas_call
    monkeypatch.setattr(ssd.pl, "pallas_call",
                        lambda *a, **kw: traced.append(1) or real(*a, **kw))
    ssd._ssd_step_call.clear_cache()

    def layers(state):
        ys = []
        for _ in range(3):
            y, state = kernel(inp, state, live, first, head_block=4)
            ys.append(y)
        return ys, state

    text = jax.jit(layers).lower(inp["state"]).as_text()
    assert len(traced) == 1
    assert text.count("call @_ssd_step_call") == 3
