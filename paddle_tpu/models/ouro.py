"""Ouro (``model_type: ouro``, arXiv:2510.25741): a looped decoder. ONE stack
of ``num_hidden_layers`` layers is run ``total_ut_steps`` times on every
token with the same weights; each pass keeps K/V of its own, and a learned
gate after each pass says when a token could leave the loop.

The equations, with ``x`` the residual stream, ``x_0 = E[ids]``; for pass
``t = 0 .. total_ut_steps - 1`` and layer ``l`` (weights independent of
``t``)::

    a = Wo Attn(RoPE(Wq n1(x)), RoPE(Wk n1(x)), Wv n1(x));   x = x + n2(a)
    m = Wdown (silu(Wgate n3(x)) * (Wup n3(x)));              x = x + n4(m)

a SANDWICH norm: four RMSNorms a layer (``rms_norm_eps``), the second and
fourth on the branch's OUTPUT before the residual add. Attention: causal,
``num_attention_heads`` heads of ``head_dim``, scale ``1 / sqrt(head_dim)``,
RoPE over the whole head (rotate-half, base ``rope_theta``), no biases. The
K and V of pass ``t``, layer ``l`` are CACHE LAYER ``t * L + l``, and the
row attends that cache layer only: no K/V is shared between passes.

After the last layer of every pass ``h_t = norm_f(x)``, and ``h_t`` IS the
next pass's input (``x = h_t``); ``lambda_t = sigmoid(w_g . h_t + b_g)``.
``logits = W_head h_last`` (untied). The gate's exit distribution is
``p(t) = lambda_t prod_{j<t} (1 - lambda_j)`` for ``t`` before the last pass
and the rest on the last; a token's EXIT STEP is the first ``t`` whose
cumulative ``p`` reaches ``early_exit_threshold``, the last pass if none
does. At the published threshold 1 every token runs every pass, and that
is the only value this program runs: a token that left early would have no
K/V in the later passes' cache layers, and what later tokens should read
there is not for a serving system to guess (:class:`LoopUnsupported`).

Departures, all noted: q, k and v are one fused matrix, as are the gate and
up projections. Precision: THE RESIDUAL STREAM IS FLOAT32 whatever the
weights' type (192 layer applications each add a unit-scale branch to a
stream that has grown to ten times that: in bfloat16 every add rounds at 3-6%
of what it adds, and at the published size the logits moved twice as far from
the float32 reference as with the stream in float32: PERF.md section 4); a
norm's output is cast to the weights' type for the product that follows;
float32 for the norms' statistics, the gate and the logits.

Serving: :meth:`OuroForCausalLM.ragged_forward` is the forward over ragged
rows that ``LLMEngine`` calls. The passes are ONE loop in the compiled
program (``lax.fori_loop`` with the pool in the carry and the cache layer
traced), so a program holds one stack's text whatever ``total_ut_steps``.
"""

from __future__ import annotations

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp

from .. import nn
from ..nn import functional as F
from ..nn import initializer as I
from ..nn.layer import Layer, LayerList
from ..ops.paged_attention import (kv_page_size, kv_write,
                                   ragged_paged_attention)
from ..ops.rotary import apply_rotary_pos_emb, rope_at
from .generation import greedy_by_forward


class LoopUnsupported(ValueError):
    """A way of running the loop that this program does not have was asked
    for. ``mechanism`` names it: ``"early_exit"`` (``early_exit_threshold``
    below 1: the K/V of the passes a token skips)."""

    def __init__(self, mechanism: str, msg: str):
        super().__init__(msg)
        self.mechanism = mechanism


@dataclass
class OuroConfig:
    """The published keys of ``config.json`` under their own names."""
    vocab_size: int = 49152
    hidden_size: int = 2048
    num_hidden_layers: int = 48
    num_attention_heads: int = 16
    num_key_value_heads: int = 16
    head_dim: int = 128
    intermediate_size: int = 5632
    rms_norm_eps: float = 1e-6
    rope_theta: float = 1e6
    max_position_embeddings: int = 65536
    total_ut_steps: int = 4
    early_exit_threshold: float = 1.0
    initializer_range: float = 0.02

    def __post_init__(self):
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("num_attention_heads is not a multiple of "
                             "num_key_value_heads")
        if self.early_exit_threshold < 1.0:
            raise LoopUnsupported(
                "early_exit",
                f"early_exit_threshold {self.early_exit_threshold} < 1: a "
                f"token that leaves the loop early writes no K/V in the "
                f"later passes' cache layers, and what later tokens read "
                f"there is undefined; only the published 1.0 (every token "
                f"runs every pass) is run")

    # what the engine asks of any model's configuration
    @property
    def num_layers(self) -> int:
        return self.num_hidden_layers

    @property
    def num_heads(self) -> int:
        return self.num_attention_heads

    @property
    def num_kv_heads(self) -> int:
        return self.num_key_value_heads

    @property
    def q_size(self) -> int:
        return self.num_attention_heads * self.head_dim

    @property
    def kv_size(self) -> int:
        return self.num_key_value_heads * self.head_dim


def exit_step(lambdas, threshold: float):
    """``lambdas`` [steps, T] -> int32 [T]: the first step whose cumulative
    exit probability ``1 - prod_{j<=t} (1 - lambda_j)`` reaches
    ``threshold``, the last step if none does."""
    steps = lambdas.shape[0]
    if steps == 1:
        return jnp.zeros(lambdas.shape[1:], jnp.int32)
    reached = 1.0 - jnp.cumprod(1.0 - lambdas[:-1], 0) >= threshold
    return jnp.where(jnp.any(reached, 0), jnp.argmax(reached, 0),
                     steps - 1).astype(jnp.int32)


def _linear(cfg, n_in, n_out):
    return nn.Linear(n_in, n_out, bias_attr=False,
                     weight_attr=I.Normal(0.0, cfg.initializer_range))


class OuroAttention(Layer):
    def __init__(self, cfg: OuroConfig):
        super().__init__()
        self.cfg = cfg
        self.qkv_proj = _linear(cfg, cfg.hidden_size,
                                cfg.q_size + 2 * cfg.kv_size)
        self.o_proj = _linear(cfg, cfg.q_size, cfg.hidden_size)

    def qkv(self, u, cos, sin):
        """Rotated ``q`` [T, heads, d], ``k`` and ``v`` [T, kv_heads, d] of
        rows ``u`` [T, H] at the positions ``cos`` / ``sin`` [T, d] are
        of."""
        cfg = self.cfg
        t = u.shape[0]
        q, k, v = jnp.split(self.qkv_proj(u),
                            [cfg.q_size, cfg.q_size + cfg.kv_size], -1)
        q = q.reshape(1, t, cfg.num_heads, cfg.head_dim)
        k = k.reshape(1, t, cfg.num_kv_heads, cfg.head_dim)
        q, k = apply_rotary_pos_emb(q, k, cos, sin)
        return q[0], k[0], v.reshape(t, cfg.num_kv_heads, cfg.head_dim)

    def forward(self, u, cos, sin):
        """One whole sequence ``u`` [S, H]: plain causal attention."""
        cfg = self.cfg
        with jax.named_scope("attn"):
            q, k, v = self.qkv(u, cos, sin)
            s = u.shape[0]
            rep = cfg.num_heads // cfg.num_kv_heads
            k, v = jnp.repeat(k, rep, 1), jnp.repeat(v, rep, 1)
            sc = jnp.einsum("qhd,khd->hqk", q.astype(jnp.float32),
                            k.astype(jnp.float32)) / math.sqrt(cfg.head_dim)
            sc = jnp.where(jnp.tril(jnp.ones((s, s), bool)), sc, -jnp.inf)
            a = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(sc, -1),
                           v.astype(jnp.float32)).astype(u.dtype)
            return self.o_proj(a.reshape(s, cfg.q_size))


class OuroMLP(Layer):
    """``Wdown (silu(Wgate x) * (Wup x))``, gate and up one matrix."""

    def __init__(self, cfg: OuroConfig):
        super().__init__()
        self.gate_up = _linear(cfg, cfg.hidden_size,
                               2 * cfg.intermediate_size)
        self.down = _linear(cfg, cfg.intermediate_size, cfg.hidden_size)
        self._scope = "mlp"

    def forward(self, x):
        return self.down(F.swiglu(self.gate_up(x)))


class OuroLayer(Layer):
    def __init__(self, cfg: OuroConfig):
        super().__init__()
        self.input_norm = nn.RMSNorm(cfg.hidden_size, cfg.rms_norm_eps)
        self.attn = OuroAttention(cfg)
        self.post_attn_norm = nn.RMSNorm(cfg.hidden_size, cfg.rms_norm_eps)
        self.pre_mlp_norm = nn.RMSNorm(cfg.hidden_size, cfg.rms_norm_eps)
        self.mlp = OuroMLP(cfg)
        self.post_mlp_norm = nn.RMSNorm(cfg.hidden_size, cfg.rms_norm_eps)
        for norm in (self.input_norm, self.post_attn_norm,
                     self.pre_mlp_norm, self.post_mlp_norm):
            norm._scope = "ln"

    def feed_forward(self, x):
        """The second half of the layer, on the float32 stream ``x``."""
        u = self.pre_mlp_norm(x).astype(self.mlp.down.weight.dtype)
        return x + self.post_mlp_norm(self.mlp(u))


class OuroForCausalLM(Layer):
    """The looped decoder with its exit gate and untied head."""

    def __init__(self, cfg: OuroConfig):
        super().__init__()
        self.cfg = cfg
        init = I.Normal(0.0, cfg.initializer_range)
        self.embed = nn.Embedding(cfg.vocab_size, cfg.hidden_size,
                                  weight_attr=init)
        self.layers = LayerList([OuroLayer(cfg)
                                 for _ in range(cfg.num_hidden_layers)])
        self.final_norm = nn.RMSNorm(cfg.hidden_size, cfg.rms_norm_eps)
        self.final_norm._scope = "ln"
        self.gate = nn.Linear(cfg.hidden_size, 1, weight_attr=init)
        self.lm_head = _linear(cfg, cfg.hidden_size, cfg.vocab_size)

    # -- shared pieces ---------------------------------------------------
    def _embed(self, tokens):
        """The residual stream's first value, float32."""
        with jax.named_scope("embed"):
            return self.embed(tokens).astype(jnp.float32)

    @property
    def _dtype(self):
        """The type the matrix products run in: the weights'."""
        return self.embed.weight.dtype

    def _end_of_pass(self, x):
        """``(h_t, lambda_t [T] float32)`` of a pass's last residual."""
        h = self.final_norm(x)
        with jax.named_scope("loop_gate"):
            lam = jax.nn.sigmoid(
                h @ self.gate.weight.astype(jnp.float32)
                + self.gate.bias.astype(jnp.float32))[:, 0]
        return h, lam

    def ragged_logits(self, hidden):
        """``hidden`` [R, H] (``h`` of the last pass: the final norm is
        already in it) -> float32 logits [R, V]."""
        with jax.named_scope("lm_head"):
            w = self.lm_head.weight
            return jnp.einsum("rh,hv->rv", hidden.astype(w.dtype), w,
                              preferred_element_type=jnp.float32)

    # -- whole sequences (tests, generate) -------------------------------
    def _sequence(self, tokens):
        """``(logits [S, V], exit step [S])`` of one sequence."""
        cfg = self.cfg
        cos, sin = rope_at(jnp.arange(tokens.shape[0]), cfg.head_dim,
                           cfg.rope_theta)
        x = self._embed(tokens)
        lambdas = []
        for _ in range(cfg.total_ut_steps):
            for layer in self.layers:
                x = x + layer.post_attn_norm(layer.attn(
                    layer.input_norm(x).astype(self._dtype), cos, sin))
                x = layer.feed_forward(x)
            x, lam = self._end_of_pass(x)
            lambdas.append(lam)
        return self.ragged_logits(x), exit_step(jnp.stack(lambdas),
                                                cfg.early_exit_threshold)

    def forward(self, input_ids):
        """``input_ids`` [B, S] -> logits [B, S, V]; no cache."""
        return jnp.stack([self._sequence(row)[0] for row in input_ids])

    def generate(self, input_ids, max_new_tokens: int = 20):
        """Greedy decoding by the whole-sequence forward
        (:func:`~paddle_tpu.models.generation.greedy_by_forward`). The
        serving path is ``LLMEngine``; this is what it is held to."""
        return greedy_by_forward(self, input_ids, max_new_tokens)

    # -- the engine's forward over ragged rows ---------------------------
    def kv_cache_spec(self):
        """``(cache layers, kv_heads, head_dim)`` of the paged K/V pool:
        a cache layer for every layer of every pass."""
        cfg = self.cfg
        return (cfg.num_hidden_layers * cfg.total_ut_steps,
                cfg.num_kv_heads, cfg.head_dim)

    def state_cache_spec(self):
        return None

    def moe_aux_spec(self):
        return None

    def loop_aux_spec(self):
        """``total_ut_steps``: :meth:`ragged_forward`'s ``aux`` is int32
        ``[T]``, each row's exit step in ``0 .. total_ut_steps - 1``."""
        return self.cfg.total_ut_steps

    def ragged_forward(self, rows, cache):
        """``rows``: ``tokens``, ``positions``, ``limits`` [T] (0 = a
        padded or inactive row, whose K/V lands on scratch page 0) and
        ``tables`` [T, pages]; ``cache``: ``k_pages``, ``v_pages`` (the
        stacked pool of ``layers x passes`` cache layers),
        ``attention_impl``. Pass ``t`` writes each row's K/V into cache
        layer ``t * layers + l`` and attends that cache layer. Returns
        ``(h of the last pass [T, H], cache, exit step [T])``."""
        cfg = self.cfg
        n_layers, steps = cfg.num_hidden_layers, cfg.total_ut_steps
        positions, limits = rows.positions, rows.limits
        ps = kv_page_size(cache.k_pages)
        tables = jnp.clip(rows.tables, 0)
        page_idx = jnp.take_along_axis(
            tables, (positions // ps)[:, None], axis=1)[:, 0]
        page_idx = jnp.where(limits > 0, page_idx, 0)   # pads -> scratch 0
        offs = positions % ps
        cos, sin = rope_at(positions, cfg.head_dim, cfg.rope_theta)

        def one_pass(t, carry):
            x, k_pages, v_pages, lambdas = carry
            for l, layer in enumerate(self.layers):
                at = t * n_layers + l
                u = layer.input_norm(x).astype(self._dtype)
                with jax.named_scope("attn"):
                    q, k, v = layer.attn.qkv(u, cos, sin)
                k_pages = kv_write(k_pages, at, page_idx, offs, k)
                v_pages = kv_write(v_pages, at, page_idx, offs, v)
                att = ragged_paged_attention(
                    q, k_pages, v_pages, tables, limits,
                    impl=cache.attention_impl, layer=at,
                    n_chunk=rows.n_chunk)
                with jax.named_scope("attn"):
                    a = layer.attn.o_proj(
                        att.reshape(-1, cfg.q_size).astype(u.dtype))
                x = layer.feed_forward(x + layer.post_attn_norm(a))
            x, lam = self._end_of_pass(x)
            return x, k_pages, v_pages, lambdas.at[t].set(lam)

        x = self._embed(rows.tokens)
        x, k_pages, v_pages, lambdas = jax.lax.fori_loop(
            0, steps, one_pass,
            (x, cache.k_pages, cache.v_pages,
             jnp.zeros((steps, x.shape[0]), jnp.float32)))
        with jax.named_scope("loop_gate"):
            aux = exit_step(lambdas, cfg.early_exit_threshold)
        return x, cache._replace(k_pages=k_pages, v_pages=v_pages), aux
