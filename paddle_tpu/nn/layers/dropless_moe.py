"""Dropless routed experts, for a chip that holds a share of them.

``MoELayer`` (moe.py) is the GShard dispatch: a static capacity an expert,
tokens over it dropped, made for training under an ``ep`` mesh axis. A
served model's mathematics has no capacity: the router scores every row over
ALL ``num_experts``, takes ``top_k``, weighs them by a softmax over those
``top_k`` scores, and every chosen expert computes its row. This layer is
that, for one chip of an expert-parallel deployment: it is told which
experts it holds (``experts_held = (first, count)``), routes over all of
them, and computes the part of the result its own experts give. What the
absent experts would add is left out here (their chip adds it, after an
exchange this layer does not stand in for): the shares of all chips sum to
the whole layer (tests/test_dropless_moe.py).

Rows are sorted by expert and each expert's group is multiplied by that
expert's weights, one grouped product a weight matrix: the work follows the
routed load, there is no capacity and no row is dropped, however uneven the
routing. The grouped product is one algorithm with two implementations:
``jax.lax.ragged_dot`` (``impl="xla"``: the path off the TPU and the
other's oracle) and the Pallas kernel ``ops/grouped_matmul.py``
(``impl="pallas"``: what an engine's programs run where the weights live on
a TPU; it reads each held expert's weights once and none of an expert
without rows).

An expert is ``W_out (silu(a) * b)`` with ``[a | b] = W_in x``.

The steps of ``forward``, and the scope each stands under in a trace:

1. ``router``: scores, ``top_k``, gates; ``group [T, k]`` (a pair that is
   not ours, or of an invalid row, takes the group behind every held
   expert); the ONE sort (``order``: pairs by group, stable) and, with no
   second sort and no scatter, its inverse ``pos [T, k]`` and the groups'
   sizes (:func:`sorted_places`: cumulative sums of a one-hot).
2. ``moe``: ``x``'s rows gathered into sorted order, the two grouped
   products (kernel ``grouped_matmul`` on a TPU) around ``silu(a) * b``:
   ``out [T*k, d]`` in the operands' dtype, sorted by expert, rows past
   ``sum(rows_held)`` unspecified.
3. ``moe/moe_combine`` (:func:`combine`): every row sums its own pairs
   where the row is: ``y[t] = sum_j held[t, j] ? gates[t, j] *
   float32(out[pos[t, j]]) : 0``: a gather of the row's ``k`` rows of
   ``out`` and a float32 sum over them, cast to ``x``'s dtype once. Read
   from the row's side because the same sum from the expert's side (a
   gate-weighted float32 copy of all ``T x k`` sorted rows, ``segment_sum``
   by row) is a scatter-add of ``d``-wide rows into computed addresses,
   which does not stream on the TPU; tests/test_dropless_moe.py keeps that
   form as the oracle: the same float32 products, added by expert there
   and by rank here.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from ...ops.grouped_matmul import grouped_matmul
from .. import initializer as I
from ..layer import Layer


def route_top_k(x, router_weight, top_k: int, select_bias=None):
    """``(expert ids [T, k], gates [T, k] float32)``: the router product in
    float32 and, without ``select_bias``, the softmax over the chosen
    scores. With ``select_bias`` [num_experts] the SIGMOID form: scores
    ``s = sigmoid(logits)``, chosen the ``top_k`` largest of ``s +
    select_bias`` (the bias balances load and is no part of a gate), gates
    ``s`` of the chosen divided by their sum."""
    logits = jnp.matmul(x.astype(jnp.float32),
                        router_weight.astype(jnp.float32),
                        precision=jax.lax.Precision.HIGHEST)
    if select_bias is None:
        top, idx = jax.lax.top_k(logits, top_k)
        return idx, jax.nn.softmax(top, axis=-1)
    scores = jax.nn.sigmoid(logits)
    _, idx = jax.lax.top_k(scores + select_bias.astype(jnp.float32), top_k)
    top = jnp.take_along_axis(scores, idx, axis=-1)
    return idx, top / jnp.sum(top, axis=-1, keepdims=True)


def sorted_places(group, n_groups: int):
    """``(pos [T, k], sizes [n_groups])``: the place of pair ``(t, j)`` in
    the stable sort of ``group.reshape(-1)`` and every group's size, with no
    second sort and no scatter: a pair stands behind every smaller group
    (``offsets``), behind the pairs of its own group in the rows before its
    own and behind those before it in its row. Cumulative sums of a one-hot
    over ``[T, n_groups]`` and over the ``k`` pairs of a row."""
    onehot = (group[..., None] == jnp.arange(n_groups, dtype=group.dtype)
              ).astype(jnp.int32)                          # [T, k, n_groups]
    in_row = jnp.sum(onehot, axis=1)                       # [T, n_groups]
    upto_row = jnp.cumsum(in_row, axis=0)
    sizes = upto_row[-1]
    before = (jnp.cumsum(sizes) - sizes)[None, None, :] \
        + (upto_row - in_row)[:, None, :] \
        + jnp.cumsum(onehot, axis=1) - onehot
    return jnp.sum(onehot * before, axis=-1), sizes


def combine(out, pos, held, gates):
    """``y [T, d]`` float32: row ``t``'s held pairs' rows of ``out [T*k,
    d]`` (the second grouped product, sorted by expert; ``pos [T, k]`` says
    where each pair's row lies) times their gates, summed over the row's
    ``k`` pairs in float32. The mask stands in front of the product: a pair
    that is not held names a row past ``sum(rows_held)``, which the grouped
    product leaves unspecified, and ``0 * NaN`` must not reach ``y``.

    Rank-major, ``[k, T, d]``, so the sum runs over the leading axis, and
    the mask in ``out``'s dtype: in this form XLA:TPU makes the gather and
    ONE fusion that converts, multiplies and adds; with the mask behind the
    product it writes a float32 ``[T*k, d]`` between the two
    (tests/test_chip_compile.py)."""
    picked = out.at[pos.T].get(mode="promise_in_bounds",
                               unique_indices=True)            # [k, T, d]
    picked = jnp.where(held.T[..., None], picked, jnp.zeros((), out.dtype))
    return jnp.sum(picked.astype(jnp.float32) * gates.T[..., None], axis=0)


class DroplessMoE(Layer):
    """``forward(x [T, d], valid [T] bool or None, impl)`` ->
    ``(y [T, d], rows_held [count] int32)``; ``rows_held[e]`` is how many
    valid rows expert ``first + e`` received. ``impl`` names the grouped
    product: ``"xla"`` or ``"pallas"``. ``routed_scaling_factor``
    multiplies every gate (a model's ``moe_routed_scaling_factor``).
    ``scoring``: ``"softmax"`` (over the chosen scores) or ``"sigmoid"``
    (:func:`route_top_k`'s second form; the layer then holds ``e_bias``
    [num_experts], float32, the selection bias)."""

    def __init__(self, d_model: int, d_expert: int, num_experts: int,
                 top_k: int,
                 experts_held: Optional[Tuple[int, int]] = None,
                 initializer_range: float = 0.02,
                 routed_scaling_factor: float = 1.0,
                 scoring: str = "softmax"):
        super().__init__()
        if scoring not in ("softmax", "sigmoid"):
            raise ValueError(f"unknown scoring {scoring!r}")
        first, count = experts_held or (0, num_experts)
        if not (0 <= first and count >= 1
                and first + count <= num_experts):
            raise ValueError(
                f"experts_held {experts_held} lies outside the "
                f"{num_experts} experts the router scores")
        if top_k > num_experts:
            raise ValueError("top_k exceeds num_experts")
        self.num_experts, self.top_k = num_experts, top_k
        self.first, self.count = first, count
        self.d_expert = d_expert
        self.routed_scaling_factor = float(routed_scaling_factor)
        init = I.Normal(0.0, initializer_range)
        self.router = self.create_parameter([d_model, num_experts],
                                            initializer=init)
        self.e_bias = None
        if scoring == "sigmoid":
            self.e_bias = self.create_parameter(
                [num_experts], dtype="float32",
                initializer=I.Normal(0.0, 0.01))
        self.w_in = self.create_parameter(
            [count, d_model, 2 * d_expert], initializer=init)
        self.w_out = self.create_parameter(
            [count, d_expert, d_model], initializer=init)

    def forward(self, x, valid=None, impl: str = "xla"):
        if impl not in ("xla", "pallas"):
            raise ValueError(f"unknown impl {impl!r}")
        product = grouped_matmul if impl == "pallas" else jax.lax.ragged_dot
        k, count = self.top_k, self.count
        with jax.named_scope("router"):
            idx, gates = route_top_k(x, self.router, k, self.e_bias)
            if self.routed_scaling_factor != 1.0:
                gates = gates * self.routed_scaling_factor
            local = idx - self.first
            held = (local >= 0) & (local < count)
            if valid is not None:
                held = held & valid[:, None]
            # a pair that is not ours sorts behind every group
            group = jnp.where(held, local, count)                  # [T, k]
            order = jnp.argsort(group.reshape(-1), stable=True)
            rows = order // k
            pos, sizes = sorted_places(group, count + 1)
            rows_held = sizes[:count]
        with jax.named_scope("moe"):
            xs = jnp.take(x, rows, axis=0)                         # [T*k, d]
            h = product(xs, self.w_in, rows_held)
            a, b = jnp.split(h, 2, axis=-1)
            out = product(jax.nn.silu(a) * b, self.w_out, rows_held)
            with jax.named_scope("moe_combine"):
                y = combine(out, pos, held, gates)
        return y.astype(x.dtype), rows_held
