"""Rotary position embeddings (RoPE).

Reference context: the reference ships RoPE via its ecosystem
(PaddleNLP fused_rope / incubate fused_rotary_position_embedding in
later versions); the core op rotates each head-dim pair (x_{2i},
x_{2i+1}) by position-dependent angles so attention scores depend only
on relative positions.

TPU-native notes: implemented in the half-split convention
(rotate_half, the LLaMA/NeoX layout) — two VPU multiplies and one
add per element, fused by XLA into the attention prologue; cos/sin
tables are precomputed once per max length and gathered per position
(static shapes, KV-cache offsets supported via ``position_ids``)."""

from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax.numpy as jnp
import numpy as np


@functools.lru_cache(maxsize=16)
def rope_tables(head_dim: int, max_len: int, base: float = 10000.0,
                dtype=jnp.float32) -> Tuple[np.ndarray, np.ndarray]:
    """cos/sin tables [max_len, head_dim] (half-split convention).
    Cached: eager decode loops call this per token per layer.

    Computed in NUMPY on purpose: jnp primitives bind to whatever
    trace is active, so a first call from inside a jit/scan trace
    would cache TRACERS and poison every later trace with an
    UnexpectedTracerError (order-dependent — an eager warm-up call
    masked it). numpy arrays are concrete constants under any trace."""
    inv = 1.0 / (base ** (np.arange(0, head_dim, 2,
                                    dtype=np.float32) / head_dim))
    t = np.arange(max_len, dtype=np.float32)
    freqs = np.outer(t, inv)                        # [L, D/2]
    emb = np.concatenate([freqs, freqs], axis=-1)   # [L, D]
    np_dtype = np.dtype(dtype) if dtype != jnp.bfloat16 else None
    cos, sin = np.cos(emb), np.sin(emb)
    if np_dtype is not None:
        return cos.astype(np_dtype), sin.astype(np_dtype)
    import ml_dtypes
    return (cos.astype(ml_dtypes.bfloat16),
            sin.astype(ml_dtypes.bfloat16))


def yarn_inv_freq(rot_dim: int, base: float, factor: float,
                  original_max_position_embeddings: int,
                  beta_fast: float = 32.0,
                  beta_slow: float = 1.0) -> np.ndarray:
    """YaRN's inverse frequencies over a rotary part of ``rot_dim``
    (float32 [rot_dim / 2]): dimension ``i`` keeps its own frequency
    ``f_i = base^(-2i / rot_dim)`` where it turns more than ``beta_fast``
    times over the original context, takes ``f_i / factor`` where it
    turns less than ``beta_slow`` times, and a linear ramp of the two in
    between. The attention factor is not in them: :func:`rope_at` takes
    it."""
    f = 1.0 / (base ** (np.arange(0, rot_dim, 2, dtype=np.float64)
                        / rot_dim))

    def turn_dim(turns):
        return rot_dim * math.log(original_max_position_embeddings
                                  / (turns * 2 * math.pi)) \
            / (2 * math.log(base))

    low = max(math.floor(turn_dim(beta_fast)), 0)
    high = min(math.ceil(turn_dim(beta_slow)), rot_dim - 1)
    ramp = (np.arange(rot_dim // 2, dtype=np.float64) - low) \
        / max(high - low, 1e-3)
    keep = 1.0 - np.clip(ramp, 0.0, 1.0)
    return ((f / factor) * (1.0 - keep) + f * keep).astype(np.float32)


def rope_at(positions, head_dim: int, base: float = 10000.0,
            inv_freq=None, attention_factor: float = 1.0):
    """cos/sin [T, head_dim] (half-split convention, float32) AT the given
    ``positions`` [T], computed in the program: what a model whose
    ``max_position_embeddings`` would make :func:`rope_tables` a constant of
    tens of megabytes takes instead. :func:`apply_rotary_pos_emb` reads
    them as tables already gathered (``position_ids`` None). ``head_dim``
    is the ROTARY part's width (a head that rotates only its first half
    passes half its size: :func:`apply_partial_rotary`); ``inv_freq``
    [head_dim / 2] takes the place of the plain ``base`` ladder
    (:func:`yarn_inv_freq`), and both tables are multiplied by
    ``attention_factor``."""
    inv = np.asarray(inv_freq, np.float32) if inv_freq is not None \
        else 1.0 / (base ** (np.arange(0, head_dim, 2,
                                       dtype=np.float32) / head_dim))
    freqs = positions.astype(jnp.float32)[:, None] * inv[None, :]
    emb = jnp.concatenate([freqs, freqs], axis=-1)
    if attention_factor == 1.0:
        return jnp.cos(emb), jnp.sin(emb)
    return jnp.cos(emb) * attention_factor, jnp.sin(emb) * attention_factor


def _rotate_half(x):
    half = x.shape[-1] // 2
    return jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)


def apply_rotary_pos_emb(q, k, cos, sin, position_ids=None):
    """Rotate q/k ([B, S, H, D]) by the table entries at
    ``position_ids`` ([B, S], default arange — pass the absolute
    positions when decoding with a KV cache)."""
    s = q.shape[1]
    # tables may arrive as numpy constants (rope_tables caches numpy —
    # trace-safe); gathering by a traced position_ids needs jnp
    cos, sin = jnp.asarray(cos), jnp.asarray(sin)
    if position_ids is None:
        cos_g = cos[None, :s, None, :]
        sin_g = sin[None, :s, None, :]
    else:
        cos_g = cos[position_ids][:, :, None, :]
        sin_g = sin[position_ids][:, :, None, :]
    q_out = q * cos_g + _rotate_half(q) * sin_g
    k_out = k * cos_g + _rotate_half(k) * sin_g
    return q_out.astype(q.dtype), k_out.astype(k.dtype)


def apply_partial_rotary(q, k, cos, sin):
    """:func:`apply_rotary_pos_emb` over the first ``cos.shape[-1]``
    dimensions of each head of ``q`` / ``k`` [B, S, H, D] (rotate-half
    inside that part); the rest of the head passes through. Tables as wide
    as the head are the whole-head case."""
    rot = cos.shape[-1]
    if rot == q.shape[-1]:
        return apply_rotary_pos_emb(q, k, cos, sin)
    qr, kr = apply_rotary_pos_emb(q[..., :rot], k[..., :rot], cos, sin)
    return (jnp.concatenate([qr, q[..., rot:]], axis=-1),
            jnp.concatenate([kr, k[..., rot:]], axis=-1))
