"""Laguna (``model_type: laguna``): a decoder whose attention layers are of
TWO KINDS in one stack (``layer_types``): ``full_attention`` and
``sliding_attention`` (a row attends the last ``sliding_window`` positions
only), each kind with its own number of query heads
(``num_attention_heads_per_layer``) and its own rotary scheme
(``rope_parameters``), every head's output scaled by a learned gate
(``gating: "per-head"``); a leading dense feed-forward layer
(``mlp_only_layers``) and then routed + shared experts.

The equations, with ``x`` the residual stream ``[T, H]`` and ``u =
RMSNorm(x)`` before each branch (pre-norm, ``rms_norm_eps``); layer ``l``
has ``n_l`` query heads, ``num_key_value_heads`` K/V heads of
``head_dim`` ``d``, no biases::

    q = u W_q [T, n_l, d];  k = u W_k,  v = u W_v [T, kv, d]
    q, k = RoPE_kind(q, k)
    o_h = softmax_j(q_h . k_j / sqrt(d)) v_j     j <= p, and on a sliding
                                                 layer also p - j < window
    g = sigmoid(u W_g) [T, n_l];   x = x + concat_h(g_h o_h) W_o
    x = x + FF_l(RMSNorm(x))

- *Rotary*. Sliding: plain RoPE over the whole head. Full: the first
  ``partial_rotary_factor * d`` dimensions of a head rotate (rotate-half
  inside that part), the rest pass through; the inverse frequencies are
  YaRN's over that part and cos / sin are multiplied by
  ``attention_factor`` (``ops/rotary.py yarn_inv_freq`` / ``rope_at``).
- *Feed-forward*. A layer in ``mlp_only_layers``: ``W_down (silu(W_gate u)
  * W_up u)`` of ``intermediate_size``. Any other: ``shared(u) +
  moe_routed_scaling_factor * sum_{e in top k} g_e E_e(u)``: the router
  scores all ``num_experts`` in float32, takes the ``num_experts_per_tok``
  largest, ``g`` = softmax over those (``norm_topk_prob``); ``shared`` and
  every ``E_e`` SwiGLU of ``shared_expert_intermediate_size`` /
  ``moe_intermediate_size``. ``Routed`` sums the chosen experts THAT ARE
  HELD HERE (``experts_held``; nn/layers/dropless_moe.py).
- Final RMSNorm, untied head over the rows of the vocabulary held here.

ASSUMED (the published ``config.json`` does not say; each a one-line change,
the same in ``benchmark/reference/laguna_swa.py``): the pre-norm residual
layout; no Q/K norm; the gate's form (sigmoid of a linear map of the normed
input, applied to each head's output before ``W_o``); softmax as the
router's activation and no router bias; no gate on the shared expert.

Departures, all noted: q, k and v are one fused matrix, as are the gate and
up projections of every feed-forward. Precision: the residual stream is
float32 whatever the weights' type (as ``models/ouro.py``); a norm's output
is cast to the weights' type for the product that follows; float32 for the
norms' statistics, the router product, the head gate, softmax and the
logits.

Serving: :meth:`LagunaForCausalLM.ragged_forward`. ``kv_cache_spec()`` names
TWO cache groups (``inference/page_pool.py``): ``full`` (the full layers:
a page lives as long as its sequence) and ``window`` (the sliding layers:
a page is freed once it lies behind the window). A sliding layer's rows
attend with a lower bound (``ragged_paged_attention(starts=)``).
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from .. import nn
from ..inference.page_pool import CacheGroup
from ..nn import functional as F
from ..nn import initializer as I
from ..nn.layer import Layer, LayerList
from ..nn.layers.dropless_moe import DroplessMoE
from ..ops.paged_attention import (kv_page_size, kv_write,
                                   ragged_paged_attention)
from ..ops.rotary import apply_partial_rotary, rope_at, yarn_inv_freq
from .generation import greedy_by_forward

FULL, SLIDING = "full_attention", "sliding_attention"

PUBLISHED_ROPE = {
    FULL: {"rope_theta": 500000, "rope_type": "yarn", "factor": 128,
           "original_max_position_embeddings": 8192, "beta_slow": 1,
           "beta_fast": 32, "attention_factor": 1.4852030263919618,
           "partial_rotary_factor": 0.5},
    SLIDING: {"rope_type": "default", "rope_theta": 10000,
              "partial_rotary_factor": 1},
}


@dataclass
class LagunaConfig:
    """The published keys of ``config.json`` under their own names, plus
    ``experts_held`` (which routed experts this chip holds; None = all).
    ``layer_types`` and ``num_attention_heads_per_layer`` are given as
    lists (as published), so any cut of depth keeps the pattern: the first
    ``num_hidden_layers`` entries are the layers built. ``vocab_size`` is
    the number of rows of the embedding and the head held here."""
    vocab_size: int = 100352
    hidden_size: int = 3072
    intermediate_size: int = 12288
    num_hidden_layers: int = 48
    num_key_value_heads: int = 8
    head_dim: int = 128
    max_position_embeddings: int = 1048576
    rms_norm_eps: float = 1e-6
    num_experts: int = 256
    num_experts_per_tok: int = 10
    moe_intermediate_size: int = 1024
    shared_expert_intermediate_size: int = 1024
    moe_routed_scaling_factor: float = 2.5
    mlp_only_layers: Sequence[int] = (0,)
    sliding_window: int = 512
    layer_types: Sequence[str] = (FULL, SLIDING, SLIDING, SLIDING) * 12
    num_attention_heads_per_layer: Sequence[int] = (48, 72, 72, 72) * 12
    rope_parameters: dict = field(
        default_factory=lambda: {k: dict(v)
                                 for k, v in PUBLISHED_ROPE.items()})
    initializer_range: float = 0.02
    experts_held: Optional[Tuple[int, int]] = None

    def __post_init__(self):
        n = self.num_hidden_layers
        self.layer_types = tuple(self.layer_types)[:n]
        self.num_attention_heads_per_layer = tuple(
            int(h) for h in self.num_attention_heads_per_layer)[:n]
        self.mlp_only_layers = tuple(int(i) for i in self.mlp_only_layers)
        if len(self.layer_types) != n \
                or len(self.num_attention_heads_per_layer) != n:
            raise ValueError("layer_types / num_attention_heads_per_layer "
                             "are shorter than num_hidden_layers")
        bad = set(self.layer_types) - {FULL, SLIDING}
        if bad:
            raise ValueError(f"unknown layer types {sorted(bad)}")
        if any(h % self.num_key_value_heads
               for h in self.num_attention_heads_per_layer):
            raise ValueError("a layer's heads are not a multiple of "
                             "num_key_value_heads")
        if self.experts_held is not None:
            self.experts_held = tuple(int(v) for v in self.experts_held)

    # what the engine asks of any model's configuration
    @property
    def num_layers(self) -> int:
        return self.num_hidden_layers

    @property
    def num_kv_heads(self) -> int:
        return self.num_key_value_heads

    @property
    def kv_size(self) -> int:
        return self.num_key_value_heads * self.head_dim

    def layers_of(self, kind: str) -> Tuple[int, ...]:
        return tuple(i for i, t in enumerate(self.layer_types) if t == kind)

    def rope(self, kind: str, positions):
        """``(cos, sin)`` [T, rotary width] of a layer of ``kind`` at
        ``positions``."""
        p = self.rope_parameters[kind]
        rot = int(self.head_dim * p.get("partial_rotary_factor", 1))
        if p.get("rope_type", "default") == "default":
            return rope_at(positions, rot, float(p["rope_theta"]))
        if p["rope_type"] != "yarn":
            raise NotImplementedError(f"rope_type {p['rope_type']!r}")
        inv = yarn_inv_freq(
            rot, float(p["rope_theta"]), float(p["factor"]),
            int(p["original_max_position_embeddings"]),
            float(p.get("beta_fast", 32)), float(p.get("beta_slow", 1)))
        return rope_at(positions, rot, inv_freq=inv,
                       attention_factor=float(p["attention_factor"]))


def _linear(cfg, n_in, n_out):
    return nn.Linear(n_in, n_out, bias_attr=False,
                     weight_attr=I.Normal(0.0, cfg.initializer_range))


class LagunaAttention(Layer):
    def __init__(self, cfg: LagunaConfig, kind: str, n_heads: int):
        super().__init__()
        self.cfg, self.kind, self.n_heads = cfg, kind, n_heads
        self.q_size = n_heads * cfg.head_dim
        self.window = cfg.sliding_window if kind == SLIDING else None
        self.qkv_proj = _linear(cfg, cfg.hidden_size,
                                self.q_size + 2 * cfg.kv_size)
        self.g_proj = _linear(cfg, cfg.hidden_size, n_heads)
        self.o_proj = _linear(cfg, self.q_size, cfg.hidden_size)

    def qkv(self, u, cos, sin):
        """Rotated ``q`` [T, heads, d], ``k`` and ``v`` [T, kv_heads, d]
        of rows ``u`` [T, H] at the positions ``cos`` / ``sin`` are of."""
        cfg = self.cfg
        t = u.shape[0]
        with jax.named_scope("attn"):
            q, k, v = jnp.split(
                self.qkv_proj(u), [self.q_size, self.q_size + cfg.kv_size],
                -1)
        with jax.named_scope("rope"):
            q, k = apply_partial_rotary(
                q.reshape(1, t, self.n_heads, cfg.head_dim),
                k.reshape(1, t, cfg.num_kv_heads, cfg.head_dim), cos, sin)
        return q[0], k[0], v.reshape(t, cfg.num_kv_heads, cfg.head_dim)

    def gate_and_project(self, att, u):
        """ASSUMED form of ``gating: "per-head"``: ``sigmoid(u W_g)``, one
        scalar a head in float32, on the head's output before ``W_o``."""
        with jax.named_scope("attn_gate"):
            g = jax.nn.sigmoid(jnp.einsum(
                "th,hn->tn", u, self.g_proj.weight,
                preferred_element_type=jnp.float32))
            att = (att.astype(jnp.float32) * g[:, :, None]).astype(u.dtype)
        with jax.named_scope("attn"):
            return self.o_proj(att.reshape(-1, self.q_size))

    def forward(self, u, cos, sin):
        """One whole sequence ``u`` [S, H]: plain masked attention."""
        cfg = self.cfg
        q, k, v = self.qkv(u, cos, sin)
        with jax.named_scope("attn"):
            s = u.shape[0]
            rep = self.n_heads // cfg.num_kv_heads
            k, v = jnp.repeat(k, rep, 1), jnp.repeat(v, rep, 1)
            sc = jnp.einsum("qhd,khd->hqk", q.astype(jnp.float32),
                            k.astype(jnp.float32)) / math.sqrt(cfg.head_dim)
            back = jnp.arange(s)[:, None] - jnp.arange(s)[None, :]
            seen = back >= 0
            if self.window is not None:
                seen = seen & (back < self.window)
            sc = jnp.where(seen, sc, -jnp.inf)
            att = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(sc, -1),
                             v.astype(jnp.float32)).astype(u.dtype)
        return self.gate_and_project(att, u)


class GatedMLP(Layer):
    """``W_out (silu(a) * b)``, ``[a | b] = W_in x``: the dense
    feed-forward (scope ``mlp``) and the shared expert."""

    def __init__(self, cfg: LagunaConfig, width: int, scope: str):
        super().__init__()
        self.w_in = _linear(cfg, cfg.hidden_size, 2 * width)
        self.w_out = _linear(cfg, width, cfg.hidden_size)
        self._scope = scope

    def forward(self, x):
        return self.w_out(F.swiglu(self.w_in(x)))


class LagunaLayer(Layer):
    def __init__(self, cfg: LagunaConfig, index: int):
        super().__init__()
        self.kind = cfg.layer_types[index]
        self.input_norm = nn.RMSNorm(cfg.hidden_size, cfg.rms_norm_eps)
        self.attn = LagunaAttention(
            cfg, self.kind, cfg.num_attention_heads_per_layer[index])
        self.post_norm = nn.RMSNorm(cfg.hidden_size, cfg.rms_norm_eps)
        self.sparse = index not in cfg.mlp_only_layers
        if self.sparse:
            self.moe = DroplessMoE(
                cfg.hidden_size, cfg.moe_intermediate_size,
                cfg.num_experts, cfg.num_experts_per_tok, cfg.experts_held,
                cfg.initializer_range, cfg.moe_routed_scaling_factor)
            self.shared = GatedMLP(
                cfg, cfg.shared_expert_intermediate_size, "shared_mlp")
        else:
            self.mlp = GatedMLP(cfg, cfg.intermediate_size, "mlp")
        for norm in (self.input_norm, self.post_norm):
            norm._scope = "ln"

    def feed_forward(self, x, dtype, valid=None, moe_impl: str = "xla"):
        """The second half of the layer on the float32 stream ``x``;
        ``(x, rows each held expert received or None)``."""
        u = self.post_norm(x).astype(dtype)
        if not self.sparse:
            return x + self.mlp(u), None
        routed, rows_held = self.moe(u, valid, moe_impl)
        return x + (routed + self.shared(u)), rows_held


class LagunaForCausalLM(Layer):
    """The decoder with its untied head."""

    def __init__(self, cfg: LagunaConfig):
        super().__init__()
        self.cfg = cfg
        init = I.Normal(0.0, cfg.initializer_range)
        self.embed = nn.Embedding(cfg.vocab_size, cfg.hidden_size,
                                  weight_attr=init)
        self.layers = LayerList([LagunaLayer(cfg, i)
                                 for i in range(cfg.num_hidden_layers)])
        self.final_norm = nn.RMSNorm(cfg.hidden_size, cfg.rms_norm_eps)
        self.final_norm._scope = "ln"
        self.lm_head = _linear(cfg, cfg.hidden_size, cfg.vocab_size)
        # (group, index in the group's stack) of each layer's K/V
        self._groups = [CacheGroup(name, len(cfg.layers_of(kind)),
                                   cfg.num_kv_heads, cfg.head_dim, window)
                        for name, kind, window in
                        (("full", FULL, None),
                         ("window", SLIDING, cfg.sliding_window))
                        if cfg.layers_of(kind)]
        kinds = [FULL if g.window is None else SLIDING
                 for g in self._groups]
        self._cache_at = [(kinds.index(t), cfg.layers_of(t).index(i))
                          for i, t in enumerate(cfg.layer_types)]

    # -- shared pieces ---------------------------------------------------
    @property
    def _dtype(self):
        """The type the matrix products run in: the weights'."""
        return self.embed.weight.dtype

    def _embed(self, tokens):
        """The residual stream's first value, float32."""
        with jax.named_scope("embed"):
            return self.embed(tokens).astype(jnp.float32)

    def ragged_logits(self, hidden):
        """``hidden`` [R, H] (before the final norm) -> float32 logits
        [R, V]."""
        x = self.final_norm(hidden)
        with jax.named_scope("lm_head"):
            w = self.lm_head.weight
            return jnp.einsum("rh,hv->rv", x.astype(w.dtype), w,
                              preferred_element_type=jnp.float32)

    # -- whole sequences (tests, generate) -------------------------------
    def _sequence(self, tokens):
        cfg = self.cfg
        positions = jnp.arange(tokens.shape[0])
        rope = {kind: cfg.rope(kind, positions)
                for kind in set(cfg.layer_types)}
        x = self._embed(tokens)
        for layer in self.layers:
            u = layer.input_norm(x).astype(self._dtype)
            x = x + layer.attn(u, *rope[layer.kind])
            x, _ = layer.feed_forward(x, self._dtype)
        return self.ragged_logits(x)

    def forward(self, input_ids):
        """``input_ids`` [B, S] -> logits [B, S, V]; no cache."""
        return jnp.stack([self._sequence(row) for row in input_ids])

    def generate(self, input_ids, max_new_tokens: int = 20):
        """Greedy decoding by the whole-sequence forward
        (:func:`~paddle_tpu.models.generation.greedy_by_forward`). The
        serving path is ``LLMEngine``; this is what it is held to."""
        return greedy_by_forward(self, input_ids, max_new_tokens)

    # -- the engine's forward over ragged rows ---------------------------
    def kv_cache_spec(self):
        """A LIST of cache groups (``inference/page_pool.py``): the full
        layers' K/V, kept as long as the sequence, and the sliding
        layers', kept for ``sliding_window`` positions. Both hold K AND
        V pages (``value_dim`` None; a group without a V names the columns
        of its one row that are the value)."""
        return list(self._groups)

    def state_cache_spec(self):
        return None

    def moe_aux_spec(self):
        """``(routed layers, held experts)``: :meth:`ragged_forward`'s
        ``aux`` is int32 ``[routed layers, held + 1]``, the rows each held
        expert received and, last, every (row, expert) pair the router
        made."""
        sparse = [l for l in self.layers if l.sparse]
        return (len(sparse), sparse[0].moe.count) if sparse else None

    def loop_aux_spec(self):
        return None

    def ragged_forward(self, rows, cache):
        """``rows``: ``tokens``, ``positions``, ``limits`` [T] (0 = a
        padded or inactive row, whose K/V lands on scratch page 0) and
        ``tables``, a tuple with one ``[T, pages]`` table a cache group;
        ``cache``: ``k_pages``, ``v_pages`` (a tuple, one stacked pool a
        group), ``attention_impl``, ``moe_impl``. A sliding layer's row at
        position ``p`` attends ``max(0, p - window + 1) <= j <= p`` of its
        group's pages; what lies before was freed by the engine and is not
        read. Returns ``(hidden [T, H], cache, aux)``."""
        cfg = self.cfg
        positions, limits = rows.positions, rows.limits
        valid = limits > 0
        k_pools, v_pools = list(cache.k_pages), list(cache.v_pages)
        ps = kv_page_size(k_pools[0])
        tables = [jnp.clip(t, 0) for t in rows.tables]
        page_idx = [jnp.where(valid, jnp.take_along_axis(
            t, (positions // ps)[:, None], axis=1)[:, 0], 0)
            for t in tables]                       # pads -> scratch 0
        offs = positions % ps
        with jax.named_scope("rope"):
            rope = {kind: cfg.rope(kind, positions)
                    for kind in set(cfg.layer_types)}
        starts = jnp.maximum(limits - cfg.sliding_window, 0)
        x = self._embed(rows.tokens)
        aux = []
        for layer, (gi, li) in zip(self.layers, self._cache_at):
            u = layer.input_norm(x).astype(self._dtype)
            q, k, v = layer.attn.qkv(u, *rope[layer.kind])
            k_pools[gi] = kv_write(k_pools[gi], li, page_idx[gi], offs, k)
            v_pools[gi] = kv_write(v_pools[gi], li, page_idx[gi], offs, v)
            att = ragged_paged_attention(
                q, k_pools[gi], v_pools[gi], tables[gi], limits,
                impl=cache.attention_impl, layer=li,
                starts=starts if layer.kind == SLIDING else None,
                n_chunk=rows.n_chunk)
            x = x + layer.attn.gate_and_project(att, u)
            x, rows_held = layer.feed_forward(x, self._dtype, valid,
                                              cache.moe_impl)
            if rows_held is not None:
                aux.append(rows_held)
        cache = cache._replace(k_pages=tuple(k_pools),
                               v_pages=tuple(v_pools))
        if not aux:
            return x, cache, None
        pairs = jnp.sum(valid).astype(jnp.int32) * cfg.num_experts_per_tok
        return x, cache, jnp.concatenate(
            [jnp.stack(aux), jnp.full((len(aux), 1), pairs, jnp.int32)], 1)
