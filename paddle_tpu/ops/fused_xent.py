"""Fused linear + cross-entropy: the LM vocab path without HBM logits.

Reference context: the reference fuses softmax+xent
(softmax_with_cross_entropy kernel, phi/kernels/gpu/cross_entropy_
kernel.cu) but still materialises the [tokens, vocab] logits produced
by the head matmul. At GPT-2-small/seq-1024 scale that buffer is the
single largest HBM tenant of the train step (PERF.md): [8192, 50304]
bf16 = 788 MB written by the matmul, read by the loss, written again as
softmax grads.

TPU-native design: the head matmul and the loss are one streaming
computation over VOCAB CHUNKS — an online logsumexp (the flash-
attention trick applied to the vocab axis):

    for each chunk c:  logits_c = h @ W_c^T        (MXU, [T, C] only)
                       m, l   <- online max/sumexp (VPU)
                       picked <- one-hot gather of label logits

so peak memory is [T, chunk] instead of [T, V]. The backward replays
the same chunks, forming softmax grads per chunk and contracting them
immediately into dh ([T, H]) and dW_c ([C, H]) — again never holding
[T, V]. Expressed with ``lax.scan`` over a reshaped [K, C, H] weight:
XLA pipelines chunk k+1's matmul against chunk k's reductions, which is
the same overlap a hand-written Pallas kernel would schedule; the
arithmetic is all MXU-shaped, so the win here is HBM footprint and
bandwidth, not issue latency.

Used by ``models.gpt.GPTFusedPretrainingCriterion`` (cfg.fused_loss):
the model returns (hidden, tied weight) and the criterion streams the
loss, so logits never exist in the training graph at all.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax


def _pick_chunk(v: int, target: int = 8192) -> int:
    return min(target, v)


def _chunks(weight, chunk):
    """[V, H] → [ceil(V/chunk), chunk, H]; pad rows are masked out of
    the logsumexp by the caller (chunking works for ANY vocab size —
    no divisor requirement, so GPT-2's unpadded 50257 still streams in
    full-width chunks)."""
    v, h = weight.shape
    pad = (-v) % chunk
    if pad:
        weight = jnp.pad(weight, ((0, pad), (0, 0)))
    return weight.reshape(-1, chunk, h)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def fused_linear_cross_entropy(hidden, weight, labels,
                               ignore_index: int = -100,
                               chunk: Optional[int] = None,
                               bias=None):
    """Mean cross-entropy of ``softmax(hidden @ weight.T + bias)``
    against ``labels`` without materialising the logits.

    hidden: [T, H] (callers flatten batch/seq); weight: [V, H] (the
    tied-embedding layout); labels: [T] int; bias: optional [V] logits
    bias (BERT's decoder bias). ``ignore_index`` rows are masked out of
    the mean (reference cross_entropy semantics).
    """
    loss, _ = _fwd_rule(hidden, weight, labels, ignore_index, chunk, bias)
    return loss


def _fwd(hidden, weight, labels, ignore_index, chunk, bias=None):
    t, h = hidden.shape
    v = weight.shape[0]
    # AMP O1 hands bf16 activations + f32 params: compute in the
    # activation dtype (bf16 MXU path, half the weight-streaming
    # bytes); residuals keep the ORIGINAL weight so dW comes back in
    # the parameter's dtype. Accumulation is f32 via
    # preferred_element_type; the stats math stays f32.
    w_compute = weight if weight.dtype == hidden.dtype else \
        weight.astype(hidden.dtype)
    c = chunk or _pick_chunk(v)
    wc = _chunks(w_compute, c)
    # bias handling is a STATIC branch: None callers (GPT) pay nothing
    bc = None if bias is None else \
        _chunks(bias.astype(jnp.float32)[:, None], c)[..., 0]  # [K, C]
    labels = labels.astype(jnp.int32)

    def body(carry, args):
        m, l, picked = carry
        if bc is None:
            w_c, off = args
        else:
            w_c, b_c, off = args
        logits = lax.dot_general(
            hidden, w_c, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)        # [T, C] f32
        if bc is not None:
            logits = logits + b_c[None, :]
        # mask vocab-pad columns out of the statistics
        col_ok = off + jax.lax.broadcasted_iota(
            jnp.int32, (1, c), 1) < v
        logits = jnp.where(col_ok, logits, -jnp.inf)
        m_new = jnp.maximum(m, logits.max(axis=-1))
        l = l * jnp.exp(m - m_new) + \
            jnp.exp(logits - m_new[:, None]).sum(axis=-1)
        # one-hot gather of this chunk's label logits
        local = labels - off
        inside = (local >= 0) & (local < c)
        picked = picked + jnp.where(
            inside,
            jnp.take_along_axis(
                logits, jnp.clip(local, 0, c - 1)[:, None],
                axis=-1)[:, 0],
            0.0)
        return (m_new, l, picked), None

    m0 = jnp.full((t,), -jnp.inf, jnp.float32)
    carry0 = (m0, jnp.zeros((t,), jnp.float32),
              jnp.zeros((t,), jnp.float32))
    offsets = jnp.arange(wc.shape[0], dtype=jnp.int32) * c
    xs = (wc, offsets) if bc is None else (wc, bc, offsets)
    (m, l, picked), _ = lax.scan(body, carry0, xs)
    lse = m + jnp.log(l)
    valid = labels != ignore_index
    per_tok = jnp.where(valid, lse - picked, 0.0)
    n = jnp.maximum(valid.sum(), 1)
    loss = per_tok.sum() / n
    return loss, (hidden, weight, labels, bias, lse, valid, n)


def _bwd(ignore_index, chunk, res, g):
    hidden, weight, labels, bias, lse, valid, n = res
    t, h = hidden.shape
    v = weight.shape[0]
    out_w_dtype = weight.dtype
    if weight.dtype != hidden.dtype:
        weight = weight.astype(hidden.dtype)
    c = chunk or _pick_chunk(v)
    wc = _chunks(weight, c)
    bc = None if bias is None else \
        _chunks(bias.astype(jnp.float32)[:, None], c)[..., 0]
    labels = labels.astype(jnp.int32)
    # d(loss)/d(logits) = (softmax - onehot) * g / n, zeroed on ignored
    scale = (jnp.where(valid, 1.0, 0.0) * g / n).astype(jnp.float32)

    def body(dh, args):
        if bc is None:
            w_c, off = args
        else:
            w_c, b_c, off = args
        logits = lax.dot_general(
            hidden, w_c, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        if bc is not None:
            logits = logits + b_c[None, :]
        col_ok = off + jax.lax.broadcasted_iota(
            jnp.int32, (1, c), 1) < v
        logits = jnp.where(col_ok, logits, -jnp.inf)
        p = jnp.exp(logits - lse[:, None])              # softmax chunk
        local = labels - off
        inside = (local >= 0) & (local < c)
        onehot_col = jnp.clip(local, 0, c - 1)
        p = p - jnp.where(
            inside[:, None] &
            (jax.lax.broadcasted_iota(jnp.int32, (t, c), 1) ==
             onehot_col[:, None]), 1.0, 0.0)
        dlog_f = p * scale[:, None]                     # [T, C] f32
        db_c = None if bc is None else dlog_f.sum(axis=0)  # [C]
        # grad matmuls run in the params' dtype (bf16 MXU path); f32
        # accumulation via preferred_element_type
        dlog = dlog_f.astype(weight.dtype)
        dh = dh + lax.dot_general(
            dlog, w_c, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)         # [T, H]
        dw_c = lax.dot_general(
            dlog, hidden.astype(weight.dtype),
            (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)         # [C, H]
        return dh, (dw_c if bc is None else (dw_c, db_c))

    offsets = jnp.arange(wc.shape[0], dtype=jnp.int32) * c
    xs = (wc, offsets) if bc is None else (wc, bc, offsets)
    dh, stacked = lax.scan(body, jnp.zeros((t, h), jnp.float32), xs)
    if bc is None:
        dw_chunks, dbias = stacked, None
    else:
        dw_chunks, db_chunks = stacked
        dbias = db_chunks.reshape(-1)[:v].astype(bias.dtype)
    dw = dw_chunks.reshape(-1, h)[:v]
    return (dh.astype(hidden.dtype), dw.astype(out_w_dtype), None,
            dbias)


# not a pallas kernel (two lax.scans over vocab chunks), so no kernel
# name: a named scope marks its operations in a device trace instead
def _fwd_rule(hidden, weight, labels, ignore_index, chunk, bias):
    with jax.named_scope("fused_xent"):
        return _fwd(hidden, weight, labels, ignore_index, chunk, bias)


def _bwd_rule(ignore_index, chunk, res, g):
    with jax.named_scope("fused_xent"):
        return _bwd(ignore_index, chunk, res, g)


fused_linear_cross_entropy.defvjp(_fwd_rule, _bwd_rule)
