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
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from ...ops.grouped_matmul import grouped_matmul
from .. import initializer as I
from ..layer import Layer


def route_top_k(x, router_weight, top_k: int):
    """``(expert ids [T, k], gates [T, k] float32)``: the router product and
    the softmax over the chosen scores in float32."""
    logits = jnp.matmul(x.astype(jnp.float32),
                        router_weight.astype(jnp.float32),
                        precision=jax.lax.Precision.HIGHEST)
    top, idx = jax.lax.top_k(logits, top_k)
    return idx, jax.nn.softmax(top, axis=-1)


class DroplessMoE(Layer):
    """``forward(x [T, d], valid [T] bool or None, impl)`` ->
    ``(y [T, d], rows_held [count] int32)``; ``rows_held[e]`` is how many
    valid rows expert ``first + e`` received. ``impl`` names the grouped
    product: ``"xla"`` or ``"pallas"``. ``routed_scaling_factor``
    multiplies every gate (a model's ``moe_routed_scaling_factor``)."""

    def __init__(self, d_model: int, d_expert: int, num_experts: int,
                 top_k: int,
                 experts_held: Optional[Tuple[int, int]] = None,
                 initializer_range: float = 0.02,
                 routed_scaling_factor: float = 1.0):
        super().__init__()
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
        self.w_in = self.create_parameter(
            [count, d_model, 2 * d_expert], initializer=init)
        self.w_out = self.create_parameter(
            [count, d_expert, d_model], initializer=init)

    def forward(self, x, valid=None, impl: str = "xla"):
        if impl not in ("xla", "pallas"):
            raise ValueError(f"unknown impl {impl!r}")
        product = grouped_matmul if impl == "pallas" else jax.lax.ragged_dot
        t, _ = x.shape
        k, count = self.top_k, self.count
        with jax.named_scope("router"):
            idx, gates = route_top_k(x, self.router, k)
            if self.routed_scaling_factor != 1.0:
                gates = gates * self.routed_scaling_factor
            local = idx - self.first
            held = (local >= 0) & (local < count)
            if valid is not None:
                held = held & valid[:, None]
            # a pair that is not ours sorts behind every group
            group = jnp.where(held, local, count).reshape(-1)      # [T*k]
            order = jnp.argsort(group, stable=True)
            rows = order // k
            sizes = jnp.zeros((count + 1,), jnp.int32).at[group].add(1)
            rows_held = sizes[:count]
        with jax.named_scope("moe"):
            xs = jnp.take(x, rows, axis=0)                         # [T*k, d]
            h = product(xs, self.w_in, rows_held)
            a, b = jnp.split(h, 2, axis=-1)
            out = product(jax.nn.silu(a) * b, self.w_out, rows_held)
            g = jnp.where(held, gates, 0.0).reshape(-1)[order]
            ours = jnp.arange(t * k) < jnp.sum(rows_held)
            out = jnp.where(ours[:, None],
                            out.astype(jnp.float32) * g[:, None], 0.0)
            y = jax.ops.segment_sum(out, rows, num_segments=t)
        return y.astype(x.dtype), rows_held
