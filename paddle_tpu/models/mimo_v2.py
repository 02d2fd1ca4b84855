"""MiMo-V2-Flash (``model_type: mimo_v2_flash``): a decoder whose attention
layers are of TWO KINDS in one stack (``hybrid_layer_pattern``: 0 full, 1
sliding), each kind with its own K/V heads (4 | 8 under 64 query heads) and
rotary base, keys of 192 beside values of 128, and on the sliding layers a
learned SINK in the softmax (``add_swa_attention_sink_bias``) over a window
of 128; a leading dense feed-forward layer (``moe_layer_freq``) and then 256
sigmoid-routed experts, top 8, no shared expert.

The equations, with ``x`` the residual stream ``[T, H]`` and ``u =
RMSNorm(x)`` (``layernorm_epsilon``) before each branch, no biases::

    kind(l) = full if hybrid_layer_pattern[l] == 0 else sliding
    q = u W_q [T, 64, 192];  k = u W_k [T, kv, 192]
    v = attention_value_scale * u W_v [T, kv, 128]     kv = 4 full | 8 sliding
    q, k: RoPE over the first int(partial_rotary_factor * 192) = 64
          dimensions (rotate-half inside them), base rope_theta full |
          swa_rope_theta sliding
    s_hj = q_h . k_j / sqrt(192),  j <= p, and on a sliding layer p - j < 128
    full:    o_h = softmax_j(s_hj) v_j
    sliding: o_h = sum_j e^{s_hj - m} v_j / (e^{b_h - m} + sum_j e^{s_hj - m})
             m = max(b_h, max_j s_hj);   b_h the head's sink, float32
    x = x + concat_h(o_h) [T, 8192] W_o
    layer with moe_layer_freq[l] == 0:  x = x + W_down(silu(W_gate u) * W_up u)
    any other: z = sigmoid(u W_r) float32 [T, 256]; chosen = top 8 of z +
        e_bias (noaux_tc; n_group 1: no group limit); g = z_chosen /
        sum(z_chosen) (norm_topk_prob; routed_scaling_factor null = 1)
        x = x + sum_e g_e E_e(u),  E_e SwiGLU of moe_intermediate_size
    final RMSNorm; untied head

``Routed`` sums the chosen experts THAT ARE HELD HERE (``experts_held``;
nn/layers/dropless_moe.py).

ASSUMED (the published ``config.json`` does not say; each a one-line change,
the same in ``benchmark/reference/mimo_v2.py``): the pre-norm residual
layout and the final norm; no Q/K norm; pattern value 1 = sliding (the
``swa_*`` keys and the published 5 : 1); the sink's form as written, one
float32 logit a query head that joins the denominator and carries no value;
``attention_value_scale`` applied to ``v`` (the same number as applied to a
head's output); ``int(0.334 * 192) = 64`` rotary dimensions, rotate-half;
``attention_chunk_size`` 128 read as the window said again; no shared
expert (``n_shared_experts`` null); the three multi-token-prediction layers
left out (the config has none of their keys; serving is one token a step).

Departures, all noted: q, k and v are one fused matrix, as are the gate and
up projections of every feed-forward. A KEY IS STORED 256 WIDE
(``MiMoV2Config.key_width``: 192 padded with zeros to whole lanes of 128;
the TPU tiles a pool's last axis to 128 whatever its logical width, so the
padding costs no byte of HBM that was not already there), and the query is
padded alike: the zeros add nothing to a score, and the scale stays
``1 / sqrt(192)``. Precision: the residual stream is float32 whatever the
weights' type (as ``models/ouro.py``); a norm's output is cast to the
weights' type for the product that follows; float32 for the norms'
statistics, the router, the sink, softmax and the logits.

Serving: :meth:`MiMoV2ForCausalLM.ragged_forward`. ``kv_cache_spec()`` names
TWO cache groups (``inference/page_pool.py``), each with K pages of
``key_width`` and V pages of ``v_head_dim``: ``full`` (a page lives as long
as its sequence) and ``window`` (a page is freed once it lies behind the
window; its layers carry a sink). The window (128) is SHORTER than a prompt
chunk (256): a chunk's first rows fall behind the window of its last rows
inside one program, which the lower bound a row (``starts=``) already says.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from .. import nn
from ..inference.page_pool import CacheGroup
from ..nn import initializer as I
from ..nn.layer import Layer, LayerList
from ..nn.layers.dropless_moe import DroplessMoE
from ..ops.paged_attention import (kv_page_size, kv_write,
                                   ragged_paged_attention)
from ..ops.rotary import apply_partial_rotary, rope_at
from .generation import greedy_by_forward
from .laguna import GatedMLP

FULL, SLIDING = "full", "sliding"
_LANES = 128
PUBLISHED_PATTERN = (0, 1, 1, 1, 1) + (0, 1, 1, 1, 1, 1) * 7 + (0,)


class Geometry(NamedTuple):
    """One kind of attention layer."""
    heads: int
    kv_heads: int
    head_dim: int
    v_head_dim: int
    theta: float
    window: Optional[int]
    sink: bool


@dataclass
class MiMoV2Config:
    """The published keys of ``config.json`` under their own names, plus
    ``experts_held`` (which routed experts this chip holds; None = all).
    ``hybrid_layer_pattern`` and ``moe_layer_freq`` are given as lists (as
    published), so any cut of depth keeps the pattern: the first
    ``num_hidden_layers`` entries are the layers built. ``vocab_size`` is
    the number of rows of the embedding and the head held here."""
    vocab_size: int = 152576
    hidden_size: int = 4096
    intermediate_size: int = 16384
    num_hidden_layers: int = 48
    num_attention_heads: int = 64
    num_key_value_heads: int = 4
    head_dim: int = 192
    v_head_dim: int = 128
    swa_num_attention_heads: int = 64
    swa_num_key_value_heads: int = 8
    swa_head_dim: int = 192
    swa_v_head_dim: int = 128
    max_position_embeddings: int = 262144
    layernorm_epsilon: float = 1e-5
    rope_theta: float = 5000000
    swa_rope_theta: float = 10000
    partial_rotary_factor: float = 0.334
    sliding_window: int = 128
    attention_value_scale: float = 0.707
    hybrid_layer_pattern: Sequence[int] = PUBLISHED_PATTERN
    add_swa_attention_sink_bias: bool = True
    add_full_attention_sink_bias: bool = False
    moe_layer_freq: Sequence[int] = (0,) + (1,) * 47
    moe_intermediate_size: int = 2048
    n_routed_experts: int = 256
    n_shared_experts: Optional[int] = None
    num_experts_per_tok: int = 8
    norm_topk_prob: bool = True
    scoring_func: str = "sigmoid"
    n_group: int = 1
    topk_group: int = 1
    topk_method: str = "noaux_tc"
    routed_scaling_factor: Optional[float] = None
    initializer_range: float = 0.02
    experts_held: Optional[Tuple[int, int]] = None

    def __post_init__(self):
        n = self.num_hidden_layers
        self.hybrid_layer_pattern = tuple(
            int(v) for v in self.hybrid_layer_pattern)[:n]
        self.moe_layer_freq = tuple(int(v) for v in self.moe_layer_freq)[:n]
        if len(self.hybrid_layer_pattern) != n \
                or len(self.moe_layer_freq) != n:
            raise ValueError("hybrid_layer_pattern / moe_layer_freq are "
                             "shorter than num_hidden_layers")
        if self.scoring_func != "sigmoid" or not self.norm_topk_prob \
                or self.topk_method != "noaux_tc":
            raise NotImplementedError(
                "the router is sigmoid scores, the top k of score + bias, "
                "renormalised over the chosen")
        if self.n_group != 1 or self.topk_group != 1:
            raise NotImplementedError("a group limit on the router's choice")
        if self.n_shared_experts:
            raise NotImplementedError("a shared expert: the published "
                                      "value is null")
        for kind in (FULL, SLIDING):
            g = self.geometry(kind)
            if g.heads % g.kv_heads:
                raise ValueError(f"{kind}: query heads are no multiple of "
                                 f"the K/V heads")
        if self.experts_held is not None:
            self.experts_held = tuple(int(v) for v in self.experts_held)

    # what the engine asks of any model's configuration
    @property
    def num_layers(self) -> int:
        return self.num_hidden_layers

    @property
    def layer_kinds(self) -> Tuple[str, ...]:
        return tuple(SLIDING if v else FULL
                     for v in self.hybrid_layer_pattern)

    def layers_of(self, kind: str) -> Tuple[int, ...]:
        return tuple(i for i, t in enumerate(self.layer_kinds) if t == kind)

    def geometry(self, kind: str) -> Geometry:
        if kind == FULL:
            return Geometry(
                self.num_attention_heads, self.num_key_value_heads,
                self.head_dim, self.v_head_dim, float(self.rope_theta), None,
                bool(self.add_full_attention_sink_bias))
        return Geometry(
            self.swa_num_attention_heads, self.swa_num_key_value_heads,
            self.swa_head_dim, self.swa_v_head_dim,
            float(self.swa_rope_theta), int(self.sliding_window),
            bool(self.add_swa_attention_sink_bias))

    def key_width(self, kind: str) -> int:
        """A key AS STORED: ``head_dim`` padded to whole lanes."""
        return -(-self.geometry(kind).head_dim // _LANES) * _LANES

    def rope(self, kind: str, positions):
        """``(cos, sin)`` [T, rotary width] of a layer of ``kind`` at
        ``positions``."""
        g = self.geometry(kind)
        rot = int(self.partial_rotary_factor * g.head_dim)
        return rope_at(positions, rot - rot % 2, g.theta)


def _linear(cfg, n_in, n_out):
    return nn.Linear(n_in, n_out, bias_attr=False,
                     weight_attr=I.Normal(0.0, cfg.initializer_range))


class MiMoAttention(Layer):
    def __init__(self, cfg: MiMoV2Config, kind: str):
        super().__init__()
        self.cfg, self.kind = cfg, kind
        g = self.geo = cfg.geometry(kind)
        self.q_size = g.heads * g.head_dim
        self.k_size = g.kv_heads * g.head_dim
        self.scale = 1.0 / math.sqrt(g.head_dim)
        self.qkv_proj = _linear(
            cfg, cfg.hidden_size,
            self.q_size + self.k_size + g.kv_heads * g.v_head_dim)
        self.o_proj = _linear(cfg, g.heads * g.v_head_dim, cfg.hidden_size)
        # one logit a query head, float32 whatever the weights' type
        self.sinks = self.create_parameter(
            [g.heads], dtype="float32",
            initializer=I.Normal(0.0, 1.0)) if g.sink else None

    def qkv(self, u, cos, sin):
        """Rotated ``q`` [T, heads, dk], ``k`` [T, kv_heads, dk] and the
        scaled ``v`` [T, kv_heads, dv] of rows ``u`` [T, H] at the positions
        ``cos`` / ``sin`` are of."""
        g = self.geo
        t = u.shape[0]
        with jax.named_scope("attn"):
            q, k, v = jnp.split(
                self.qkv_proj(u), [self.q_size, self.q_size + self.k_size],
                -1)
            v = (v.astype(jnp.float32) * self.cfg.attention_value_scale) \
                .astype(u.dtype)
        with jax.named_scope("rope"):
            q, k = apply_partial_rotary(
                q.reshape(1, t, g.heads, g.head_dim),
                k.reshape(1, t, g.kv_heads, g.head_dim), cos, sin)
        return q[0], k[0], v.reshape(t, g.kv_heads, g.v_head_dim)

    def project(self, att):
        with jax.named_scope("attn"):
            return self.o_proj(att.reshape(att.shape[0], -1))

    def forward(self, u, cos, sin):
        """One whole sequence ``u`` [S, H]: plain masked attention, the
        sink one more column of the softmax that no value follows."""
        g = self.geo
        q, k, v = self.qkv(u, cos, sin)
        with jax.named_scope("attn"):
            s = u.shape[0]
            rep = g.heads // g.kv_heads
            k, v = jnp.repeat(k, rep, 1), jnp.repeat(v, rep, 1)
            sc = jnp.einsum("qhd,khd->hqk", q.astype(jnp.float32),
                            k.astype(jnp.float32)) * self.scale
            back = jnp.arange(s)[:, None] - jnp.arange(s)[None, :]
            seen = back >= 0
            if g.window is not None:
                seen = seen & (back < g.window)
            sc = jnp.where(seen, sc, -jnp.inf)
            if self.sinks is not None:
                sc = jnp.concatenate([sc, jnp.broadcast_to(
                    self.sinks.astype(jnp.float32)[:, None, None],
                    (g.heads, s, 1))], -1)
            p = jax.nn.softmax(sc, -1)[..., :s]
            att = jnp.einsum("hqk,khd->qhd", p,
                             v.astype(jnp.float32)).astype(u.dtype)
        return self.project(att)


class MiMoLayer(Layer):
    def __init__(self, cfg: MiMoV2Config, index: int):
        super().__init__()
        self.kind = cfg.layer_kinds[index]
        eps = cfg.layernorm_epsilon
        self.input_norm = nn.RMSNorm(cfg.hidden_size, eps)
        self.attn = MiMoAttention(cfg, self.kind)
        self.post_norm = nn.RMSNorm(cfg.hidden_size, eps)
        self.sparse = bool(cfg.moe_layer_freq[index])
        if self.sparse:
            self.moe = DroplessMoE(
                cfg.hidden_size, cfg.moe_intermediate_size,
                cfg.n_routed_experts, cfg.num_experts_per_tok,
                cfg.experts_held, cfg.initializer_range,
                cfg.routed_scaling_factor or 1.0, scoring="sigmoid")
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
        return x + routed, rows_held


class MiMoV2ForCausalLM(Layer):
    """The decoder with its untied head."""

    def __init__(self, cfg: MiMoV2Config):
        super().__init__()
        self.cfg = cfg
        init = I.Normal(0.0, cfg.initializer_range)
        self.embed = nn.Embedding(cfg.vocab_size, cfg.hidden_size,
                                  weight_attr=init)
        self.layers = LayerList([MiMoLayer(cfg, i)
                                 for i in range(cfg.num_hidden_layers)])
        self.final_norm = nn.RMSNorm(cfg.hidden_size, cfg.layernorm_epsilon)
        self.final_norm._scope = "ln"
        self.lm_head = _linear(cfg, cfg.hidden_size, cfg.vocab_size)
        kinds = [kind for kind in (FULL, SLIDING) if cfg.layers_of(kind)]
        self._groups = []
        for kind in kinds:
            g = cfg.geometry(kind)
            self._groups.append(CacheGroup(
                "full" if kind == FULL else "window",
                len(cfg.layers_of(kind)), g.kv_heads, cfg.key_width(kind),
                g.window, None, g.v_head_dim, g.sink))
        # (group, index in the group's stack) of each layer's K/V
        self._cache_at = [(kinds.index(t), cfg.layers_of(t).index(i))
                          for i, t in enumerate(cfg.layer_kinds)]

    # -- shared pieces ---------------------------------------------------
    @property
    def _dtype(self):
        """The type the matrix products run in: the weights'."""
        return self.embed.weight.dtype

    @property
    def experts_held(self) -> Tuple[int, int]:
        """``(first, count)`` of the routed experts held here."""
        return self.cfg.experts_held or (0, self.cfg.n_routed_experts)

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
                for kind in set(cfg.layer_kinds)}
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
        layers' K/V, kept as long as the sequence, and the sliding layers',
        kept for ``sliding_window`` positions and attended through a sink.
        Both hold K pages ``key_width`` wide (192 stored as 256) beside V
        pages of ``v_head_dim`` (128)."""
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
        group, V narrower than K), ``attention_impl``, ``moe_impl``. A
        sliding layer's row at position ``p`` attends ``max(0, p - window +
        1) <= j <= p`` of its group's pages and its head's sink; what lies
        before was freed by the engine and is not read. Returns ``(hidden
        [T, H], cache, aux)``."""
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
                    for kind in set(cfg.layer_kinds)}
        starts = jnp.maximum(limits - cfg.sliding_window, 0)
        x = self._embed(rows.tokens)
        aux = []
        for layer, (gi, li) in zip(self.layers, self._cache_at):
            attn = layer.attn
            u = layer.input_norm(x).astype(self._dtype)
            with jax.named_scope("attn_full" if layer.kind == FULL
                                 else "attn_window"):
                q, k, v = attn.qkv(u, *rope[layer.kind])
                # a key as stored: whole lanes, the query padded alike
                pad = ((0, 0), (0, 0),
                       (0, cfg.key_width(layer.kind) - q.shape[-1]))
                q, k = jnp.pad(q, pad), jnp.pad(k, pad)
                k_pools[gi] = kv_write(k_pools[gi], li, page_idx[gi], offs,
                                       k)
                v_pools[gi] = kv_write(v_pools[gi], li, page_idx[gi], offs,
                                       v)
                att = ragged_paged_attention(
                    q, k_pools[gi], v_pools[gi], tables[gi], limits,
                    scale=attn.scale, impl=cache.attention_impl, layer=li,
                    starts=starts if layer.kind == SLIDING else None,
                    n_chunk=rows.n_chunk, sinks=attn.sinks)
                x = x + attn.project(att)
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
