"""Greedy decoding by a model's whole-sequence forward: what the serving
path of a model that keeps no cache of its own is held to."""

from __future__ import annotations

import jax
import jax.numpy as jnp


def greedy_by_forward(net, input_ids, max_new_tokens: int = 20):
    """``input_ids`` [B, S] -> [B, S + max_new_tokens]: ``net.forward`` over
    a buffer of the final length (causal: what lies after a position cannot
    move it), one compiled program for every step."""
    from ..nn.layer import functional_call, split_state
    net.eval()
    b, s = input_ids.shape
    buf = jnp.zeros((b, s + max_new_tokens), jnp.int32) \
        .at[:, :s].set(input_ids)
    params, buffers = split_state(net)

    @jax.jit
    def step(params, buf, n):
        logits, _ = functional_call(net, params, buffers, buf,
                                    training=False)
        nxt = jnp.argmax(
            jnp.take_along_axis(
                logits, jnp.full((b, 1, 1), n - 1), axis=1)[:, 0], -1)
        return buf.at[:, n].set(nxt.astype(jnp.int32))

    for n in range(s, s + max_new_tokens):
        buf = step(params, buf, n)
    return buf
