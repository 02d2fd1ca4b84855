"""nn/layers/dropless_moe.py (float32 on the CPU): no token is ever dropped,
and the shares of an expert-parallel stage add up to the whole layer, with
either grouped product: ``jax.lax.ragged_dot`` (``"xla"``) and the Pallas
kernel ``ops/grouped_matmul.py`` through the interpreter (``"pallas"``)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu.nn import DroplessMoE
from paddle_tpu.nn.layers import dropless_moe as dm

D, DE, E, K, T = 32, 16, 8, 2, 40
# float32 sums of the same K products in another order
TOL = 2e-6


def _dense(layer, x, experts=None):
    """The definition, an expert at a time, over ``experts`` (ids)."""
    logits = x @ layer.router
    top, idx = jax.lax.top_k(logits, layer.top_k)
    gates = jax.nn.softmax(top, -1)
    y = jnp.zeros_like(x)
    for e in (range(layer.num_experts) if experts is None else experts):
        le = e - layer.first
        a, b = jnp.split(x @ layer.w_in[le], 2, -1)
        gate = jnp.sum(jnp.where(idx == e, gates, 0.0), -1, keepdims=True)
        y = y + gate * ((jax.nn.silu(a) * b) @ layer.w_out[le])
    return y


def _layer(held=None, seed=0):
    pt.seed(seed)
    return DroplessMoE(D, DE, E, K, held, initializer_range=0.3)


def _x(seed=1, t=T):
    return jnp.asarray(np.random.default_rng(seed).normal(size=(t, D)),
                       jnp.float32)


IMPLS = pytest.mark.parametrize("impl", ["xla", "pallas"])


@IMPLS
def test_matches_the_definition_and_counts_every_pair(impl):
    layer, x = _layer(), _x()
    y, rows = layer(x, impl=impl)
    np.testing.assert_allclose(y, _dense(layer, x), atol=TOL, rtol=TOL)
    assert int(rows.sum()) == T * K


@IMPLS
def test_a_router_that_sends_every_row_to_one_expert_loses_no_token(impl):
    layer, x = _layer(), _x()
    # expert 3 wins every row by a wide margin, expert 5 comes second
    router = np.zeros((D, E), np.float32)
    layer.router = jnp.asarray(router)
    x = x.at[:, 0].set(1.0)
    layer.router = layer.router.at[0, 3].set(50.0).at[0, 5].set(20.0)
    y, rows = layer(x, impl=impl)
    assert rows.tolist() == [0, 0, 0, T, 0, T, 0, 0]
    np.testing.assert_allclose(y, _dense(layer, x), atol=TOL, rtol=TOL)
    assert float(jnp.min(jnp.max(jnp.abs(y), -1))) > 0   # every row served


@IMPLS
def test_invalid_rows_are_not_counted_and_get_nothing(impl):
    layer, x = _layer(), _x()
    valid = jnp.arange(T) % 3 != 0
    y, rows = layer(x, valid, impl)
    assert int(rows.sum()) == int(valid.sum()) * K
    np.testing.assert_allclose(y[valid], _dense(layer, x)[valid], atol=TOL,
                               rtol=TOL)
    assert float(jnp.max(jnp.abs(y[~valid]))) == 0.0


@IMPLS
@pytest.mark.parametrize("split", [4, 2, 7])
def test_the_shares_of_a_stage_add_up_to_the_whole_layer(split, impl):
    """Experts 0..split-1 on one chip, the rest on the other: each routes
    over all E and computes its own experts' part; the parts sum to the
    uncut layer, and the pairs they count to every pair."""
    whole, x = _layer(), _x()
    parts, counted = [], 0
    for first, count in ((0, split), (split, E - split)):
        share = _layer((first, count))
        share.router = whole.router
        share.w_in = whole.w_in[first:first + count]
        share.w_out = whole.w_out[first:first + count]
        y, rows = share(x, impl=impl)
        np.testing.assert_allclose(
            y, _dense(whole, x, range(first, first + count)), atol=TOL,
            rtol=TOL)
        parts.append(y)
        counted += int(rows.sum())
    np.testing.assert_allclose(parts[0] + parts[1], whole(x, impl=impl)[0],
                               atol=TOL, rtol=TOL)
    assert counted == T * K


def test_experts_held_outside_the_router_is_refused():
    with pytest.raises(ValueError, match="experts_held"):
        DroplessMoE(D, DE, E, K, (6, 4))


def test_an_unknown_grouped_product_is_refused():
    with pytest.raises(ValueError, match="unknown impl"):
        _layer()(_x(), impl="cuda")


@IMPLS
def test_eight_shares_and_one_shared_expert_sum_to_the_uncut_layer(impl):
    """A stage of eight chips, one expert each here, gates times a routed
    scaling factor of 2.5; what every chip computes alike (a shared expert)
    is counted ONCE. The parts add up to the uncut layer's ``shared(x) +
    2.5 * sum_e g_e E_e(x)``, and the pairs they count to every pair."""
    pt.seed(0)
    whole = DroplessMoE(D, DE, E, K, initializer_range=0.3,
                        routed_scaling_factor=2.5)
    x = _x()
    w_shared = jnp.asarray(
        np.random.default_rng(3).normal(size=(D, D)) * 0.1, jnp.float32)
    shared = jnp.tanh(x @ w_shared)
    total, counted = shared, 0
    for first in range(E):
        share = DroplessMoE(D, DE, E, K, (first, 1), initializer_range=0.3,
                            routed_scaling_factor=2.5)
        share.router = whole.router
        share.w_in = whole.w_in[first:first + 1]
        share.w_out = whole.w_out[first:first + 1]
        y, rows = share(x, impl=impl)
        total = total + y
        counted += int(rows.sum())
    np.testing.assert_allclose(total, shared + 2.5 * _dense(whole, x),
                               atol=5 * TOL, rtol=5 * TOL)
    np.testing.assert_allclose(whole(x, impl=impl)[0],
                               2.5 * _dense(whole, x), atol=5 * TOL,
                               rtol=5 * TOL)
    assert counted == T * K


# -- the combine: a row sums its own k pairs where it is (PR 38) -------------


def _scatter_combine(out, held, gates, order):
    """The combine as it stood before PR 38, kept as the oracle: the
    gate-weighted float32 copy of all ``T x k`` sorted rows under the mask
    ``ours``, scatter-added by row."""
    t, k = held.shape
    g = jnp.where(held, gates, 0.0).reshape(-1)[order]
    ours = jnp.arange(t * k) < jnp.sum(held)
    out = jnp.where(ours[:, None], out.astype(jnp.float32) * g[:, None], 0.0)
    return jax.ops.segment_sum(out, order // k, num_segments=t)


def _scatter_form(layer, x, valid=None, impl="xla"):
    """``DroplessMoE.forward`` around :func:`_scatter_combine`, float32
    ``y`` (no cast) and the groups' sizes by a scatter-add of ones."""
    product = dm.grouped_matmul if impl == "pallas" else jax.lax.ragged_dot
    k, count = layer.top_k, layer.count
    idx, gates = dm.route_top_k(x, layer.router, k)
    gates = gates * layer.routed_scaling_factor
    local = idx - layer.first
    held = (local >= 0) & (local < count)
    if valid is not None:
        held = held & valid[:, None]
    group = jnp.where(held, local, count).reshape(-1)
    order = jnp.argsort(group, stable=True)
    rows_held = jnp.zeros((count + 1,), jnp.int32).at[group].add(1)[:count]
    h = product(jnp.take(x, order // k, axis=0), layer.w_in, rows_held)
    a, b = jnp.split(h, 2, axis=-1)
    out = product(jax.nn.silu(a) * b, layer.w_out, rows_held)
    return _scatter_combine(out, held, gates, order), rows_held


def _poisoned(product):
    """``product`` with what its contract leaves unspecified made hostile:
    the rows past ``sum(group_sizes)`` are NaN and inf by turns."""
    def stub(lhs, rhs, group_sizes, **kw):
        out = product(lhs, rhs, group_sizes, **kw)
        at = jnp.arange(out.shape[0])[:, None]
        poison = jnp.where(at % 2 == 0, jnp.nan, jnp.inf).astype(out.dtype)
        return jnp.where(at >= jnp.sum(group_sizes), poison, out)
    return stub


@IMPLS
@pytest.mark.parametrize("held", [None, (2, 3), (6, 2)],
                         ids=["every-pair-held", "a-share-held",
                              "another-share"])
def test_the_combine_is_the_definition_over_the_held_experts(held, impl):
    layer, x = _layer(held), _x()
    y, rows = layer(x, impl=impl)
    first, count = held or (0, E)
    np.testing.assert_allclose(
        y, _dense(layer, x, range(first, first + count)), atol=TOL, rtol=TOL)
    want, want_rows = _scatter_form(layer, x, None, impl)
    np.testing.assert_allclose(y, want, atol=TOL, rtol=TOL)
    assert rows.tolist() == want_rows.tolist()


@IMPLS
def test_rows_with_no_pair_on_a_held_expert_get_exactly_zero(impl):
    """Experts 6 and 7 win every row: the share (2, 3) holds none of a
    row's pairs, counts nothing and adds nothing, whatever the grouped
    product leaves in rows it was not asked for."""
    layer, x = _layer((2, 3)), _x().at[:, 0].set(1.0)
    layer.router = jnp.zeros((D, E)).at[0, 6].set(50.0).at[0, 7].set(20.0)
    y, rows = layer(x, impl=impl)
    assert rows.tolist() == [0, 0, 0]
    assert float(jnp.max(jnp.abs(y))) == 0.0


@IMPLS
def test_what_the_product_leaves_unspecified_never_reaches_y(impl,
                                                             monkeypatch):
    """``grouped_matmul`` and ``ragged_dot`` promise nothing about the rows
    past ``sum(rows_held)``: with NaN and inf there (both products of the
    layer), ``y`` is the clean layer's to the bit, an invalid row's and a
    row without a held pair exactly zero."""
    layer, x = _layer((2, 3)), _x()
    valid = jnp.arange(T) % 3 != 0
    clean, clean_rows = layer(x, valid, impl)
    monkeypatch.setattr(dm, "grouped_matmul", _poisoned(dm.grouped_matmul))
    monkeypatch.setattr(jax.lax, "ragged_dot", _poisoned(jax.lax.ragged_dot))
    y, rows = layer(x, valid, impl)
    assert int(rows.sum()) < T * K          # there ARE rows past the held
    assert bool(jnp.all(jnp.isfinite(y)))
    assert np.array_equal(np.asarray(y), np.asarray(clean))
    assert rows.tolist() == clean_rows.tolist()
    assert float(jnp.max(jnp.abs(y[~valid]))) == 0.0
    np.testing.assert_allclose(y[valid], _dense(layer, x, range(2, 5))[valid],
                               atol=TOL, rtol=TOL)


def _routing(kind, t, k, experts, count, seed=0):
    """``group [t, k]`` as the router scope makes it, from a seeded draw."""
    rng = np.random.default_rng(seed)
    logits = rng.normal(size=(t, experts))
    if kind == "uneven":
        logits[:, 1] += 4.0                # expert 1 takes nearly every row
        logits[: t // 2, 0] -= 9.0         # expert 0 none of the first half
    elif kind == "one-expert-takes-all":
        logits[:, 2] += 50.0
        logits[:, 3:count] -= 50.0         # and some held experts nothing
    idx = np.argsort(-logits, axis=1)[:, :k]
    return jnp.asarray(np.where(idx < count, idx, count), jnp.int32)


@pytest.mark.parametrize("kind", ["even", "uneven", "one-expert-takes-all"])
@pytest.mark.parametrize("t,k,experts,count", [(40, 2, 8, 8), (37, 3, 8, 5),
                                               (320, 10, 72, 36)])
def test_pos_is_the_inverse_of_the_one_sort(kind, t, k, experts, count):
    group = _routing(kind, t, k, experts, count)
    order = np.asarray(jnp.argsort(group.reshape(-1), stable=True))
    pos, sizes = dm.sorted_places(group, count + 1)
    pos = np.asarray(pos).reshape(-1)
    assert sorted(pos.tolist()) == list(range(t * k))      # a permutation
    assert np.array_equal(pos[order], np.arange(t * k))
    assert sizes.tolist() == np.bincount(np.asarray(group).reshape(-1),
                                         minlength=count + 1).tolist()


@IMPLS
def test_bf16_at_a_cells_rows_is_the_scatter_form_reordered(impl):
    """The hybrid cell's mixed tick (T 320, k 10, 36 held of 72) at a small
    width, bf16 as the engine runs it: the same float32 products of the same
    bf16 rows and float32 gates, added by rank where the scatter form added
    them by expert; one cast at the end."""
    t, k, d, de, experts, count = 320, 10, 128, 64, 72, 36
    pt.seed(0)
    layer = DroplessMoE(d, de, experts, k, (0, count), initializer_range=0.3)
    layer.w_in = layer.w_in.astype(jnp.bfloat16)
    layer.w_out = layer.w_out.astype(jnp.bfloat16)
    x = jnp.asarray(np.random.default_rng(1).normal(size=(t, d)),
                    jnp.bfloat16)
    valid = jnp.arange(t) % 7 != 0
    y, rows = layer(x, valid, impl)
    want, want_rows = _scatter_form(layer, x, valid, impl)
    assert y.dtype == jnp.bfloat16 and rows.tolist() == want_rows.tolist()
    assert 0.4 < int(rows.sum()) / (int(valid.sum()) * k) < 0.6
    scale = float(jnp.max(jnp.abs(want)))
    # float32 sums of at most ten addends in another order, then ONE
    # rounding to bf16: a ulp of bf16 where the two sums straddle a tie
    np.testing.assert_allclose(y.astype(jnp.float32),
                               want.astype(jnp.bfloat16).astype(jnp.float32),
                               atol=scale * 2 ** -8, rtol=2 ** -7)
    # and before the cast, float32-reordering distance
    idx, gates = dm.route_top_k(x, layer.router, k)
    held = (idx < count) & valid[:, None]
    group = jnp.where(held, idx, count)
    pos, _ = dm.sorted_places(group, count + 1)
    order = jnp.argsort(group.reshape(-1), stable=True)
    out = jnp.asarray(np.random.default_rng(2).normal(size=(t * k, d)),
                      jnp.bfloat16)
    got = dm.combine(out, pos, held, gates)
    ref = _scatter_combine(out, held, gates, order)
    assert got.dtype == jnp.float32
    np.testing.assert_allclose(got, ref, atol=4e-7 * float(
        jnp.max(jnp.abs(ref))), rtol=0)


@IMPLS
def test_forward_holds_no_scatter_and_no_float32_copy_of_the_pairs(impl):
    """The structure, from the traced program: nothing scatter-adds (the
    combine reads from the row's side, the group sizes are sums of a
    one-hot) and no float32 array has the sorted pairs' shape ``[T*k, d]``;
    the combine stands under its own scope inside ``moe``."""
    # more pairs than a row tile of the kernel (128), whose float32
    # accumulator is a tile's, not the pairs'
    t = 100
    layer, x = _layer((2, 3)), _x(t=t).astype(jnp.bfloat16)
    # bf16 as an engine runs it: a float32 array of the pairs' shape is a
    # copy somebody made
    layer.w_in = layer.w_in.astype(jnp.bfloat16)
    layer.w_out = layer.w_out.astype(jnp.bfloat16)
    valid = jnp.arange(t) % 3 != 0
    pairs = f"f32[{t * K},{D}]"
    text = str(jax.make_jaxpr(lambda x, v: layer(x, v, impl))(x, valid))
    assert "scatter" not in text
    assert pairs not in text
    assert pairs in str(jax.make_jaxpr(
        lambda x, v: _scatter_form(layer, x, v, impl))(x, valid))
    lowered = jax.jit(lambda x, v: layer(x, v, impl)).lower(x, valid)
    assert "moe/moe_combine" in lowered.as_text(debug_info=True)


# -- the sigmoid form: scores, a selection bias outside the gates (PR 39) ----


def _sigmoid_layer(held=None, experts=16, k=4, scale=2.446):
    pt.seed(0)
    layer = DroplessMoE(D, DE, experts, k, held, initializer_range=0.3,
                        routed_scaling_factor=scale, scoring="sigmoid")
    # a bias large enough to change who is chosen
    layer.e_bias = jnp.asarray(
        np.random.default_rng(7).normal(size=(experts,)) * 0.5, jnp.float32)
    return layer


def _dense_sigmoid(layer, x, experts=None):
    """The definition: scores sigmoid over all experts, the ``k`` largest
    of score + bias, gates the chosen SCORES over their sum, times the
    scale; an expert at a time."""
    scores = jax.nn.sigmoid(x @ layer.router)
    _, idx = jax.lax.top_k(scores + layer.e_bias, layer.top_k)
    top = jnp.take_along_axis(scores, idx, -1)
    gates = top / top.sum(-1, keepdims=True) * layer.routed_scaling_factor
    y = jnp.zeros_like(x)
    for e in (range(layer.num_experts) if experts is None else experts):
        le = e - layer.first
        a, b = jnp.split(x @ layer.w_in[le], 2, -1)
        gate = jnp.sum(jnp.where(idx == e, gates, 0.0), -1, keepdims=True)
        y = y + gate * ((jax.nn.silu(a) * b) @ layer.w_out[le])
    return y


@IMPLS
def test_sigmoid_scores_with_a_bias_that_selects_and_does_not_weigh(impl):
    layer, x = _sigmoid_layer(), _x()
    y, rows = layer(x, impl=impl)
    np.testing.assert_allclose(y, _dense_sigmoid(layer, x), atol=5 * TOL,
                               rtol=5 * TOL)
    assert int(rows.sum()) == T * 4
    idx, gates = dm.route_top_k(x, layer.router, 4, layer.e_bias)
    # renormalised: a row's gates sum to one before the scale
    np.testing.assert_allclose(gates.sum(-1), 1.0, atol=1e-6)
    # the bias chooses: without it other experts win somewhere
    plain, _ = dm.route_top_k(x, layer.router, 4, jnp.zeros((16,)))
    assert not np.array_equal(np.sort(idx, -1), np.sort(plain, -1))
    # and it is in no gate: the chosen scores alone weigh
    scores = jax.nn.sigmoid(x @ layer.router)
    top = jnp.take_along_axis(scores, idx, -1)
    np.testing.assert_allclose(gates, top / top.sum(-1, keepdims=True),
                               atol=1e-6)
    # the softmax form is what it was: no bias, no parameter for one
    assert _layer().e_bias is None
    assert "e_bias" not in _layer().state_dict()
    assert layer.state_dict()["e_bias"].dtype == jnp.float32


@IMPLS
def test_sixteen_shares_and_one_shared_expert_sum_to_the_uncut_layer(impl):
    """A slice of sixteen chips that share a sigmoid-routed layer, one
    expert each here, the shared expert computed alike by all and counted
    ONCE: the parts add up to the uncut layer's ``shared(x) + 2.446 *
    sum_e g_e E_e(x)`` and the pairs they count to every pair."""
    whole, x = _sigmoid_layer(), _x()
    w_shared = jnp.asarray(
        np.random.default_rng(3).normal(size=(D, D)) * 0.1, jnp.float32)
    shared = jnp.tanh(x @ w_shared)
    total, counted = shared, 0
    for first in range(16):
        share = _sigmoid_layer((first, 1))
        share.router, share.e_bias = whole.router, whole.e_bias
        share.w_in = whole.w_in[first:first + 1]
        share.w_out = whole.w_out[first:first + 1]
        y, rows = share(x, impl=impl)
        total = total + y
        counted += int(rows.sum())
    np.testing.assert_allclose(total, shared + _dense_sigmoid(whole, x),
                               atol=5 * TOL, rtol=5 * TOL)
    assert counted == T * 4


def test_an_unknown_scoring_is_refused():
    with pytest.raises(ValueError, match="unknown scoring"):
        DroplessMoE(D, DE, E, K, scoring="tanh")
