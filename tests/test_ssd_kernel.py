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


# ---------------------------------------------------------------------------
# ssd_chunk_kernel: the chunk scan over a layer's whole state array, against
# ssd_chunked (its oracle, over gathered rows) and the recurrence

CH, CP, CN, CSLOTS, CG = 8, 8, 16, 6, 4
# the plain form's own distance from the recurrence (tests/test_ssd_ops.py)
CHUNK_TOL = 2e-5

# lengths of the sequences in the run, padded rows after them: the layouts
# tests/test_ssd_ops.py walks, and a run of eight short sequences
LAYOUTS = {
    "one_fills_the_chunk": ((23,), 0),
    "four_and_padding": ((5, 11, 1, 9), 6),
    "two_halves": ((16, 16), 0),
    "one_without_rows": ((3, 0, 20), 1),
    "eight_short": ((2, 5, 1, 3, 4, 1, 6, 2), 0),
}


def chunk_inputs(seed, t, dtype=jnp.float32):
    r = np.random.default_rng(seed)
    return dict(
        x=jnp.asarray(r.normal(size=(t, CH, CP)), jnp.float32).astype(dtype),
        dt=jnp.asarray(np.exp(r.uniform(np.log(1e-3), np.log(1e-1),
                                        (t, CH))), jnp.float32),
        A=-jnp.asarray(r.uniform(1, 16, (CH,)), jnp.float32),
        B=jnp.asarray(r.normal(size=(t, CN)), jnp.float32).astype(dtype),
        C=jnp.asarray(r.normal(size=(t, CN)), jnp.float32).astype(dtype),
        D=jnp.asarray(r.normal(size=(CH,)), jnp.float32))


def layout(name, seed=0):
    """``(tok_seg [T], seg_rows [G], fresh [G], lens)``: the sequences of a
    layout on distinct state rows drawn from the seed, every third one
    that has rows starting in this run; unused places name the scratch
    row."""
    lens, pad = LAYOUTS[name]
    g = max(CG, len(lens))
    slots = max(CSLOTS, len(lens))
    tok = np.full((sum(lens) + pad,), g, np.int32)
    tok[:sum(lens)] = np.repeat(np.arange(len(lens)), lens)
    rows = np.full((g,), slots, np.int32)
    rows[:len(lens)] = np.random.default_rng(seed).permutation(slots)[
        :len(lens)]
    fresh = np.zeros((g,), bool)
    fresh[1:len(lens):3] = True
    fresh[:len(lens)] &= np.asarray(lens) > 0     # it starts with a row
    return jnp.asarray(tok), jnp.asarray(rows), jnp.asarray(fresh), lens


def whole_state(seed, slots):
    return jnp.asarray(np.random.default_rng(seed).normal(
        size=(slots + 1, CH, CP, CN)), jnp.float32)


def chunk_plain(inp, state, tok, rows, fresh):
    """What ``ragged_forward`` does off the TPU."""
    return ssd.ssd_chunk_gathered(**inp, state=state, tok_seg=tok,
                                  seg_rows=rows, fresh=fresh)


def chunk_kernel(inp, state, tok, rows, fresh, **kw):
    return ssd.ssd_chunk_kernel(**inp, state=state, tok_seg=tok,
                                seg_rows=rows, fresh=fresh, interpret=True,
                                **kw)


def by_recurrence(inp, state, tok, rows, fresh, lens):
    """Each sequence alone, token by token, from its own row."""
    ys, lo = [], 0
    for g, n in enumerate(lens):
        sl = slice(lo, lo + n)
        s0 = jnp.where(fresh[g], 0.0, state[rows[g]])
        y, s = ssd.ssd_recurrence(
            inp["x"][sl], inp["dt"][sl], inp["A"], inp["B"][sl],
            inp["C"][sl], inp["D"], s0)
        ys.append(y)
        if n:
            state = state.at[rows[g]].set(s)
        lo += n
    return jnp.concatenate(ys), state


@pytest.mark.parametrize("head_block", [8, 4, 1, None],
                         ids=["hb8_all", "hb4", "hb1", "hb_from_shapes"])
@pytest.mark.parametrize("name", list(LAYOUTS))
def test_chunk_kernel_matches_ssd_chunked_and_the_recurrence(name,
                                                             head_block):
    tok, rows, fresh, lens = layout(name, seed=1)
    inp = chunk_inputs(7, tok.shape[0])
    state = whole_state(3, int(rows.max()))
    y, new = chunk_kernel(inp, state, tok, rows, fresh,
                          head_block=head_block)
    assert y.dtype == jnp.float32 and new.dtype == jnp.float32
    n_live = sum(lens)
    for want_y, want_s in (chunk_plain(inp, state, tok, rows, fresh),
                           by_recurrence(inp, state, tok, rows, fresh,
                                         lens)):
        np.testing.assert_allclose(y[:n_live], want_y[:n_live],
                                   atol=CHUNK_TOL, rtol=CHUNK_TOL)
        np.testing.assert_allclose(new, want_s, atol=CHUNK_TOL,
                                   rtol=CHUNK_TOL)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("name", ["one_fills_the_chunk", "four_and_padding",
                                  "eight_short"])
def test_chunk_kernel_is_no_farther_from_the_recurrence_than_the_plain_form(
        name, dtype):
    """Float32 accuracy in every product: on the same inputs the kernel's
    worst error against the token-by-token recurrence is no larger than
    ``ssd_chunked``'s (a hair's room for the other order of the same
    sums). One bf16 pass anywhere would read a thousand times that."""
    tok, rows, fresh, lens = layout(name, seed=2)
    inp = chunk_inputs(11, tok.shape[0], dtype)
    state = whole_state(5, int(rows.max()))
    n_live = sum(lens)
    ref_y, ref_s = by_recurrence(inp, state, tok, rows, fresh, lens)

    def worst(y, s):
        return max(float(jnp.abs(y[:n_live] - ref_y).max()),
                   float(jnp.abs(s - ref_s).max()))

    plain = worst(*chunk_plain(inp, state, tok, rows, fresh))
    got = worst(*chunk_kernel(inp, state, tok, rows, fresh))
    assert got <= max(1.5 * plain, 2e-6), (got, plain)


@pytest.mark.parametrize("head_block", [8, 2], ids=["hb8", "hb2"])
@pytest.mark.parametrize("name", list(LAYOUTS))
def test_sequences_start_from_zeros_or_hold_their_bytes(name, head_block):
    """A ``fresh`` sequence's old state is never read (NaN under it, a
    finite scan from zeros out of it); a sequence without rows, every row
    no sequence of the run names, and the scratch row keep their bytes
    exactly, NaN payloads included."""
    tok, rows, fresh, lens = layout(name, seed=3)
    inp = chunk_inputs(13, tok.shape[0])
    state = whole_state(9, int(rows.max()))
    present = np.zeros((state.shape[0],), bool)
    unread = np.zeros((state.shape[0],), bool)
    for g, n in enumerate(lens):
        present[int(rows[g])] = n > 0
        unread[int(rows[g])] = n > 0 and bool(fresh[g])
    untouched = jnp.asarray(~present)
    poisoned = jnp.where(jnp.asarray(~present | unread)[:, None, None, None],
                         jnp.nan, state)
    want_y, want_s = chunk_plain(inp, jnp.nan_to_num(poisoned), tok, rows,
                                 fresh)
    y, new = chunk_kernel(inp, poisoned, tok, rows, fresh,
                          head_block=head_block)
    assert np.array_equal(np.asarray(new[untouched]).view(np.uint32),
                          np.asarray(poisoned[untouched]).view(np.uint32))
    assert np.isfinite(np.asarray(new[~untouched])).all()
    np.testing.assert_allclose(new[~untouched], want_s[~untouched],
                               atol=CHUNK_TOL, rtol=CHUNK_TOL)
    n_live = sum(lens)
    np.testing.assert_allclose(y[:n_live], want_y[:n_live], atol=CHUNK_TOL,
                               rtol=CHUNK_TOL)


def test_a_chunk_without_rows_moves_nothing():
    """The padding tick of a slab: every row of the chunk padded."""
    tok, rows, fresh, _ = layout("four_and_padding")
    tok = jnp.full_like(tok, rows.shape[0])
    inp = chunk_inputs(17, tok.shape[0])
    state = jnp.where(jnp.arange(CSLOTS + 1)[:, None, None, None] % 2 == 0,
                      jnp.nan, whole_state(1, CSLOTS))
    y, new = chunk_kernel(inp, state, tok, rows, fresh, head_block=4)
    assert np.array_equal(np.asarray(new).view(np.uint32),
                          np.asarray(state).view(np.uint32))
    np.testing.assert_array_equal(
        y, inp["D"][None, :, None] * inp["x"])


@pytest.mark.parametrize("chunk", [8, 16, 256],
                         ids=["rows_8_a_call", "rows_16_a_call", "one_call"])
def test_a_run_split_across_two_chunks_carries_its_state(chunk):
    """One prompt over two chunks (two calls, the state row carried in the
    array between them) gives what one scan of the whole run gives; so does
    a run longer than ``chunk``, walked ``chunk`` rows a call, where a
    sequence is fresh only in the call that holds its first row."""
    n, cut, row = 30, 13, 2
    inp = chunk_inputs(19, n)
    state = whole_state(21, CSLOTS)
    rows = jnp.asarray([row] + [CSLOTS] * (CG - 1), jnp.int32)
    fresh = jnp.asarray([True] + [False] * (CG - 1))
    tok = jnp.zeros((n,), jnp.int32)
    want_y, want_s = by_recurrence(inp, state, tok, rows, fresh, (n,))

    def part(sl):
        return {k: (v[sl] if v.shape[0] == n else v) for k, v in inp.items()}

    y1, mid = chunk_kernel(part(slice(0, cut)), state, tok[:cut], rows,
                           fresh, chunk=chunk)
    y2, new = chunk_kernel(part(slice(cut, n)), mid, tok[cut:], rows,
                           jnp.zeros_like(fresh), chunk=chunk)
    np.testing.assert_allclose(jnp.concatenate([y1, y2]), want_y,
                               atol=CHUNK_TOL, rtol=CHUNK_TOL)
    np.testing.assert_allclose(new, want_s, atol=CHUNK_TOL, rtol=CHUNK_TOL)
    # two sequences, the second fresh and entirely past the first call
    tok2 = jnp.asarray([0] * 11 + [1] * 19, jnp.int32)
    rows2 = rows.at[1].set(4)
    fresh2 = jnp.asarray([False, True] + [False] * (CG - 2))
    want_y, want_s = by_recurrence(inp, state, tok2, rows2, fresh2, (11, 19))
    y, new = chunk_kernel(inp, state, tok2, rows2, fresh2, chunk=chunk)
    np.testing.assert_allclose(y, want_y, atol=CHUNK_TOL, rtol=CHUNK_TOL)
    np.testing.assert_allclose(new, want_s, atol=CHUNK_TOL, rtol=CHUNK_TOL)


@pytest.mark.parametrize("head_block", [8, 2], ids=["hb8", "hb2"])
def test_inside_a_scan_the_chunk_kernels_state_is_the_carry(head_block):
    """The slab: mixed ticks of one program, the state carried from tick to
    tick under ``lax.scan``, sequences entering, continuing and a tick
    without chunk rows between them."""
    names = ("two_halves", "one_fills_the_chunk", "four_and_padding")
    t = 32
    toks, rowss, freshs = [], [], []
    for i, name in enumerate(names):
        tok, rows, fresh, _ = layout(name, seed=i)
        toks.append(jnp.concatenate(
            [tok, jnp.full((t - tok.shape[0],), CG, jnp.int32)]))
        rowss.append(rows)
        freshs.append(fresh)
    toks.append(jnp.full((t,), CG, jnp.int32))     # no prompt rows at all
    rowss.append(rowss[0])
    freshs.append(jnp.zeros_like(freshs[0]))
    inp = chunk_inputs(23, t)
    xs = jnp.asarray(np.random.default_rng(29).normal(
        size=(len(toks), t, CH, CP)), jnp.float32)
    per_tick = (xs, jnp.stack(toks), jnp.stack(rowss), jnp.stack(freshs))

    def run(step):
        def tick(state, at):
            x, tok, rows, fresh = at
            y, state = step(dict(inp, x=x), state, tok, rows, fresh)
            return state, jnp.where((tok < CG)[:, None, None], y, 0.0)
        return jax.jit(lambda s: jax.lax.scan(tick, s, per_tick))

    state = whole_state(31, CSLOTS)
    want_s, want_y = run(chunk_plain)(state)
    got_s, got_y = run(lambda *a: chunk_kernel(*a, head_block=head_block))(
        state)
    np.testing.assert_allclose(got_y, want_y, atol=CHUNK_TOL, rtol=CHUNK_TOL)
    np.testing.assert_allclose(got_s, want_s, atol=CHUNK_TOL, rtol=CHUNK_TOL)


def test_the_chunk_kernels_state_is_its_own_output():
    """``input_output_aliases`` names the state (operand 16 of the call:
    eight prefetched vectors and eight small operands come first) as
    output 1, and a program that donates it gets the same answer and takes
    the buffer."""
    tok, rows, fresh, lens = layout("four_and_padding", seed=4)
    inp = chunk_inputs(37, tok.shape[0])
    state = whole_state(41, CSLOTS)
    calls = [e for e in jax.make_jaxpr(
        lambda s: ssd._ssd_chunk_call.__wrapped__(
            inp["x"], inp["dt"], inp["A"], inp["B"], inp["C"], s, tok, rows,
            fresh, head_block=4, interpret=True))(state).jaxpr.eqns
        if e.primitive.name == "pallas_call"]
    assert len(calls) == 1
    assert tuple(calls[0].params["input_output_aliases"]) == ((16, 1),)
    assert calls[0].invars[16].aval.shape == state.shape
    assert calls[0].params["name"] == "ssd_chunk"   # the trace's name
    fn = jax.jit(lambda s: chunk_kernel(inp, s, tok, rows, fresh),
                 donate_argnums=(0,))
    want_y, want_s = chunk_plain(inp, state, tok, rows, fresh)
    donated = jnp.array(state)
    y, new = fn(donated)
    assert donated.is_deleted()
    n_live = sum(lens)
    np.testing.assert_allclose(new, want_s, atol=CHUNK_TOL, rtol=CHUNK_TOL)
    np.testing.assert_allclose(y[:n_live], want_y[:n_live], atol=CHUNK_TOL,
                               rtol=CHUNK_TOL)


def test_one_trace_of_the_chunk_kernel_serves_every_layer(monkeypatch):
    tok, rows, fresh, _ = layout("two_halves")
    inp = chunk_inputs(43, tok.shape[0])
    traced = []
    real = ssd.pl.pallas_call
    monkeypatch.setattr(ssd.pl, "pallas_call",
                        lambda *a, **kw: traced.append(1) or real(*a, **kw))
    ssd._ssd_chunk_call.clear_cache()

    def layers(state):
        ys = []
        for _ in range(3):
            y, state = chunk_kernel(inp, state, tok, rows, fresh,
                                    head_block=4)
            ys.append(y)
        return ys, state

    text = jax.jit(layers).lower(whole_state(47, CSLOTS)).as_text()
    assert len(traced) == 1
    assert text.count("call @_ssd_chunk_call") == 3


def test_the_chunk_kernel_refuses_heads_it_cannot_split():
    tok, rows, fresh, _ = layout("two_halves")
    inp = chunk_inputs(53, tok.shape[0])
    with pytest.raises(ValueError, match="do not split"):
        chunk_kernel(inp, whole_state(1, CSLOTS), tok, rows, fresh,
                     head_block=3)
