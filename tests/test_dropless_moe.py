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
