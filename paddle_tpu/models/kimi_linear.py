"""Kimi Linear (``model_type: kimi_linear``): a decoder whose layers are
Kimi Delta Attention mixers (KDA: a delta-rule state with a decay a channel)
with a latent-attention mixer (MLA) every fourth layer
(``linear_attn_config``: ``kda_layers`` / ``full_attn_layers``, numbered
from 1), a leading dense feed-forward layer (``first_k_dense_replace``) and
then sigmoid-routed + shared experts. No rotary anywhere (``mla_use_nope``):
the MLA layers carry no position, the recurrent layers do.

The equations, with ``x`` the residual stream ``[T, H]`` and ``u =
RMSNorm(x)`` before each branch (pre-norm, ``rms_norm_eps``), no biases
but the two named::

    x = x + Mixer_l(u);    x = x + FF_l(RMSNorm(x))

- *KDA mixer* (``num_heads`` heads of ``head_dim`` ``d``, a head at a
  time): ``q, k = L2norm(silu(conv(u W_q))), L2norm(silu(conv(u W_k)))``,
  ``v = silu(conv(u W_v))``, the convolution depthwise and causal over the
  last ``short_conv_kernel_size`` positions; ``log a = -exp(A_log[h]) *
  softplus((u W_f1) W_f2 + dt_bias)`` a CHANNEL, float32; ``b = sigmoid(u
  W_b)`` a head; ``S_t = (I - b k k^T) Diag(a) S_{t-1} + b k v^T``, ``o =
  S_t^T q / sqrt(d)`` (ops/kda.py); ``y = (RMSNorm_head(o) * sigmoid((u
  W_g1) W_g2 + c_g)) W_o``.
- *MLA mixer* (``num_attention_heads`` heads): ``q = u W_q`` ``[heads,
  qk_nope + qk_rope]``; ``[c | r] = u W_kva`` ``[kv_lora_rank + qk_rope]``,
  ``c <- RMSNorm(c)``; ``[k_nope_h | v_h] = c W_kvb,h``, ``k_h = [k_nope_h
  | r]`` (``r`` shared by the heads and NOT rotated), causal softmax of
  ``q_h . k_h / sqrt(qk_nope + qk_rope)``, ``y = concat_h(sum p v_h) W_o``.
  Served ABSORBED (the same numbers): a token caches ``[c | r]`` and
  nothing else; ``q~_h = [W_kvb,h^K^T q_nope,h | q_r,h]`` attends the rows
  as they lie, the probabilities sum the rows' first ``kv_lora_rank``
  columns, and ``W_kvb,h^V`` follows the sum.
- *Feed-forward*. Layer 1: ``W_down (silu(W_gate u) * W_up u)`` of
  ``intermediate_size``. Any other: ``shared(u) + routed_scaling_factor *
  sum_{e in top k} g_e E_e(u)``: scores ``s = sigmoid(u W_r)`` over all
  ``num_experts`` in float32, the ``num_experts_per_token`` largest of ``s
  + e_bias``, ``g = s`` of those over their sum (``moe_renormalize``);
  ``Routed`` sums the chosen experts THAT ARE HELD HERE (``experts_held``;
  nn/layers/dropless_moe.py).
- Final RMSNorm, untied head over the rows of the vocabulary held here.

ASSUMED (the published ``config.json`` does not say; each a one-line
change, the same in ``benchmark/reference/kimi_linear.py``): the pre-norm
residual layout and the final norm; ``A_log`` one a head, ``dt_bias`` a
channel and the decay's low-rank pair; the output gate's low-rank pair and
its bias; L2 norm of q and k after silu; the convolution on q, k and v
without bias; RMSNorm_head's weight one a channel of a head; one group for
``use_grouped_topk`` (``num_expert_group`` 1: no group limit).

Departures, all noted: q, k and v of a KDA layer are one fused matrix, as
are the gate and up projections of every feed-forward; the stored latent
row is padded with zeros to whole lanes of 128 (``latent_width``: the TPU
tiles the pool's last axis to 128 whatever the logical width, so the
padding costs no byte that was not already there). Precision: the residual
stream is float32 whatever the weights' type (as ``models/ouro.py``); a
norm's output is cast to the weights' type for the product that follows;
float32 for the norms' statistics, the convolution's sum, q / k / v of the
delta rule, the decay and its state, the gates, the router, softmax and the
logits.

Serving: :meth:`KimiLinearForCausalLM.ragged_forward`. ``kv_cache_spec()``
names ONE LATENT cache group (``inference/page_pool.py``: a row a token,
no V, no head axis); ``state_cache_spec()`` one ``conv_state`` /
``ssm_state`` row a slot for every KDA layer.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from .. import nn
from ..inference.page_pool import CacheGroup
from ..nn import functional as F
from ..nn import initializer as I
from ..nn.layer import Layer, LayerList
from ..nn.layers.dropless_moe import DroplessMoE
from ..ops import kda, ssd
from ..ops.paged_attention import kv_write, ragged_paged_attention
from .generation import greedy_by_forward
from .laguna import GatedMLP

# sequences one packed run of prompt rows may hold: the chunk form gathers
# this many carried states (ops/kda.py), so the engine packs no more
MAX_CHUNK_SEQUENCES = 8
_LANES = 128

PUBLISHED_LINEAR = {
    "full_attn_layers": [4, 8, 12, 16, 20, 24, 27], "head_dim": 128,
    "kda_layers": [1, 2, 3, 5, 6, 7, 9, 10, 11, 13, 14, 15, 17, 18, 19, 21,
                   22, 23, 25, 26],
    "num_heads": 32, "short_conv_kernel_size": 4}


@dataclass
class KimiLinearConfig:
    """The published keys of ``config.json`` under their own names, plus
    ``experts_held`` (which routed experts this chip holds; None = all) and
    ``decay_rank`` / ``gate_rank`` (ASSUMED: the low-rank pairs' inner
    width). The first ``num_hidden_layers`` layers are built, each of the
    kind ``linear_attn_config`` gives its number (from 1). ``vocab_size``
    is the number of rows of the embedding and the head held here."""
    vocab_size: int = 163840
    hidden_size: int = 2304
    intermediate_size: int = 9216
    num_hidden_layers: int = 27
    num_attention_heads: int = 32
    kv_lora_rank: int = 512
    q_lora_rank: Optional[int] = None
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    mla_use_nope: bool = True
    linear_attn_config: dict = field(
        default_factory=lambda: {k: list(v) if isinstance(v, list) else v
                                 for k, v in PUBLISHED_LINEAR.items()})
    first_k_dense_replace: int = 1
    moe_intermediate_size: int = 1024
    num_experts: int = 256
    num_experts_per_token: int = 8
    num_shared_experts: int = 1
    routed_scaling_factor: float = 2.446
    moe_renormalize: bool = True
    moe_router_activation_func: str = "sigmoid"
    rms_norm_eps: float = 1e-5
    model_max_length: int = 1048576
    decay_rank: int = 128
    gate_rank: int = 128
    initializer_range: float = 0.02
    experts_held: Optional[Tuple[int, int]] = None

    def __post_init__(self):
        if self.q_lora_rank is not None:
            raise NotImplementedError("q_lora_rank: the published value is "
                                      "null (a full-rank query projection)")
        if not self.mla_use_nope:
            raise NotImplementedError("mla_use_nope false: a rotated r")
        if self.moe_router_activation_func != "sigmoid" \
                or not self.moe_renormalize:
            raise NotImplementedError(
                "the router is sigmoid scores renormalised over the chosen")
        lin = self.linear_attn_config
        numbers = range(1, self.num_hidden_layers + 1)
        kinds = tuple("kda" if n in lin["kda_layers"] else
                      "mla" if n in lin["full_attn_layers"] else None
                      for n in numbers)
        if None in kinds:
            raise ValueError("linear_attn_config names no kind for layer "
                             f"{kinds.index(None) + 1}")
        self.layer_kinds = kinds
        if self.experts_held is not None:
            self.experts_held = tuple(int(v) for v in self.experts_held)

    # what the engine asks of any model's configuration
    @property
    def num_layers(self) -> int:
        return self.num_hidden_layers

    @property
    def max_position_embeddings(self) -> int:
        return self.model_max_length

    @property
    def kda_heads(self) -> int:
        return int(self.linear_attn_config["num_heads"])

    @property
    def kda_head_dim(self) -> int:
        return int(self.linear_attn_config["head_dim"])

    @property
    def kda_inner(self) -> int:
        return self.kda_heads * self.kda_head_dim

    @property
    def conv_kernel(self) -> int:
        return int(self.linear_attn_config["short_conv_kernel_size"])

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def latent_dim(self) -> int:
        """``[c | r]``: what a token's cached row holds."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def latent_width(self) -> int:
        """The row as stored: ``latent_dim`` padded to whole lanes."""
        return -(-self.latent_dim // _LANES) * _LANES

    def layers_of(self, kind: str) -> Tuple[int, ...]:
        return tuple(i for i, t in enumerate(self.layer_kinds) if t == kind)


def _linear(cfg, n_in, n_out):
    return nn.Linear(n_in, n_out, bias_attr=False,
                     weight_attr=I.Normal(0.0, cfg.initializer_range))


def _f32_product(x, weight):
    return jnp.einsum("ti,io->to", x, weight,
                      preferred_element_type=jnp.float32)


# the state-space reference implementation's initialisers: dt = exp(U(log
# 1e-3, log 1e-1)) through the inverse softplus; A ~ U(1, 16)
def dt_bias_init(shape, dtype):
    dt = jnp.exp(I.Uniform(math.log(1e-3), math.log(1e-1))(
        shape, jnp.float32))
    return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)


def a_log_init(shape, dtype):
    return jnp.log(I.Uniform(1.0, 16.0)(shape, jnp.float32)).astype(dtype)


class KDAMixer(Layer):
    def __init__(self, cfg: KimiLinearConfig):
        super().__init__()
        self.cfg = cfg
        nh, inner = cfg.kda_heads, cfg.kda_inner
        self.qkv_proj = _linear(cfg, cfg.hidden_size, 3 * inner)
        self.conv_weight = self.create_parameter(
            [cfg.conv_kernel, 3 * inner],
            initializer=I.Normal(0.0, cfg.initializer_range))
        self.f_a = _linear(cfg, cfg.hidden_size, cfg.decay_rank)
        self.f_b = _linear(cfg, cfg.decay_rank, inner)

        self.dt_bias = self.create_parameter([inner], dtype="float32",
                                             initializer=dt_bias_init)
        self.A_log = self.create_parameter([nh], dtype="float32",
                                           initializer=a_log_init)
        self.b_proj = _linear(cfg, cfg.hidden_size, nh)
        self.g_a = _linear(cfg, cfg.hidden_size, cfg.gate_rank)
        self.g_b = _linear(cfg, cfg.gate_rank, inner)
        self.g_bias = self.create_parameter(
            [inner], dtype="float32", initializer=I.Constant(0.0))
        self.o_norm_weight = self.create_parameter(
            [cfg.kda_head_dim], initializer=I.Constant(1.0))
        self.o_proj = _linear(cfg, inner, cfg.hidden_size)

    def gates(self, u, valid=None):
        """``(log_a [T, heads, d], b [T, heads])`` float32: the decay a
        channel, in log space, and the write strength a head. A row that
        is not ``valid`` gets 0 and 0: it moves no state."""
        cfg = self.cfg
        with jax.named_scope("gate"):
            f = _f32_product(self.f_a(u), self.f_b.weight) \
                + self.dt_bias.astype(jnp.float32)
            log_a = -jnp.exp(self.A_log.astype(jnp.float32))[None, :, None] \
                * jax.nn.softplus(f).reshape(-1, cfg.kda_heads,
                                             cfg.kda_head_dim)
            b = jax.nn.sigmoid(_f32_product(u, self.b_proj.weight))
            if valid is not None:
                log_a = jnp.where(valid[:, None, None], log_a, 0.0)
                b = jnp.where(valid[:, None], b, 0.0)
            return log_a, b

    def qkv(self, conv_out):
        """``(q, k, v)`` [T, heads, d] float32 from the convolution's
        float32 output: silu, L2 norm of q and k, q scaled by
        ``1 / sqrt(d)``."""
        cfg = self.cfg
        q, k, v = jnp.split(
            jax.nn.silu(conv_out).reshape(-1, 3 * cfg.kda_heads,
                                          cfg.kda_head_dim), 3, axis=1)
        return (kda.l2norm(q) * cfg.kda_head_dim ** -0.5, kda.l2norm(k), v)

    def finish(self, o, u):
        """A head's norm, the output gate, the output projection."""
        cfg = self.cfg
        with jax.named_scope("gate"):
            g = jax.nn.sigmoid(
                _f32_product(self.g_a(u), self.g_b.weight)
                + self.g_bias.astype(jnp.float32))
            o = F.rms_norm(o, self.o_norm_weight.astype(jnp.float32),
                           cfg.rms_norm_eps)
            y = (o.reshape(-1, cfg.kda_inner) * g).astype(u.dtype)
        return self.o_proj(y)

    def forward(self, u):
        """One whole sequence ``u`` [S, H] from a zero state."""
        cfg = self.cfg
        seg = jnp.zeros((u.shape[0],), jnp.int32)
        log_a, b = self.gates(u)
        with jax.named_scope("conv"):
            tail = jnp.zeros((1, cfg.conv_kernel - 1, 3 * cfg.kda_inner),
                             u.dtype)
            conv, _ = ssd.causal_conv_chunk(
                self.qkv_proj(u), self.conv_weight,
                jnp.zeros((), jnp.float32), tail, seg)
        with jax.named_scope("kda_chunk"):
            state = jnp.zeros((1, cfg.kda_heads, cfg.kda_head_dim,
                               cfg.kda_head_dim), jnp.float32)
            o, _ = kda.kda_chunked(*self.qkv(conv), log_a, b, state, seg)
        return self.finish(o, u)


class MLAMixer(Layer):
    def __init__(self, cfg: KimiLinearConfig):
        super().__init__()
        self.cfg = cfg
        n = cfg.num_attention_heads
        self.q_proj = _linear(cfg, cfg.hidden_size, n * cfg.qk_head_dim)
        self.kv_a = _linear(cfg, cfg.hidden_size, cfg.latent_dim)
        self.kv_norm_weight = self.create_parameter(
            [cfg.kv_lora_rank], initializer=I.Constant(1.0))
        self.kv_b = _linear(cfg, cfg.kv_lora_rank,
                            n * (cfg.qk_nope_head_dim + cfg.v_head_dim))
        self.o_proj = _linear(cfg, n * cfg.v_head_dim, cfg.hidden_size)
        self.scale = cfg.qk_head_dim ** -0.5

    def queries(self, u):
        cfg = self.cfg
        return self.q_proj(u).reshape(-1, cfg.num_attention_heads,
                                      cfg.qk_head_dim)

    def latent(self, u):
        """``[c | r]`` [T, latent_dim] of rows ``u``, ``c`` normed: what a
        token caches."""
        cfg = self.cfg
        c, r = jnp.split(self.kv_a(u), [cfg.kv_lora_rank], -1)
        c = F.rms_norm(c, self.kv_norm_weight, cfg.rms_norm_eps)
        return jnp.concatenate([c.astype(u.dtype), r], -1)

    @property
    def _kv_b(self):
        """``(W^K [c, heads, nope], W^V [c, heads, v])`` of ``kv_b``."""
        cfg = self.cfg
        w = self.kv_b.weight.reshape(
            cfg.kv_lora_rank, cfg.num_attention_heads, -1)
        return w[..., :cfg.qk_nope_head_dim], w[..., cfg.qk_nope_head_dim:]

    def absorb(self, q):
        """``q~`` [T, heads, latent_width]: a head's query against the
        latent rows as they lie, ``[W^K_h^T q_nope_h | q_r_h | 0]``."""
        cfg = self.cfg
        with jax.named_scope("absorb"):
            q_nope, q_r = jnp.split(q, [cfg.qk_nope_head_dim], -1)
            q_c = jnp.einsum("thn,chn->thc", q_nope, self._kv_b[0],
                             preferred_element_type=jnp.float32)
            pad = jnp.zeros(q.shape[:2] + (cfg.latent_width
                                           - cfg.latent_dim,), q.dtype)
            return jnp.concatenate([q_c.astype(q.dtype), q_r, pad], -1)

    def project(self, att):
        """The heads' sums over ``c`` [T, heads, kv_lora_rank] through
        ``W^V`` and the output projection."""
        cfg = self.cfg
        with jax.named_scope("absorb"):
            w = self._kv_b[1]
            out = jnp.einsum("thc,chv->thv", att.astype(w.dtype), w,
                             preferred_element_type=jnp.float32)
        return self.o_proj(out.reshape(
            -1, cfg.num_attention_heads * cfg.v_head_dim).astype(w.dtype))

    def forward(self, u):
        """One whole sequence ``u`` [S, H], EXPANDED: every token's K and V
        of every head out of its latent row, plain causal attention."""
        cfg = self.cfg
        with jax.named_scope("mla"):
            s = u.shape[0]
            q = self.queries(u).astype(jnp.float32)
            row = self.latent(u)
            c, r = jnp.split(row, [cfg.kv_lora_rank], -1)
            w_k, w_v = self._kv_b
            k_nope = jnp.einsum("sc,chn->shn", c, w_k,
                                preferred_element_type=jnp.float32)
            v = jnp.einsum("sc,chv->shv", c, w_v,
                           preferred_element_type=jnp.float32)
            k = jnp.concatenate([k_nope, jnp.broadcast_to(
                r.astype(jnp.float32)[:, None], k_nope.shape[:2]
                + (cfg.qk_rope_head_dim,))], -1)
            sc = jnp.einsum("qhd,khd->hqk", q, k) * self.scale
            sc = jnp.where(jnp.tril(jnp.ones((s, s), bool)), sc, -jnp.inf)
            att = jnp.einsum("hqk,khv->qhv", jax.nn.softmax(sc, -1), v)
            return self.o_proj(att.reshape(s, -1).astype(u.dtype))


class KimiLinearLayer(Layer):
    def __init__(self, cfg: KimiLinearConfig, index: int):
        super().__init__()
        self.kind = cfg.layer_kinds[index]
        self.input_norm = nn.RMSNorm(cfg.hidden_size, cfg.rms_norm_eps)
        self.mixer = KDAMixer(cfg) if self.kind == "kda" else MLAMixer(cfg)
        self.post_norm = nn.RMSNorm(cfg.hidden_size, cfg.rms_norm_eps)
        self.sparse = index >= cfg.first_k_dense_replace
        if self.sparse:
            self.moe = DroplessMoE(
                cfg.hidden_size, cfg.moe_intermediate_size, cfg.num_experts,
                cfg.num_experts_per_token, cfg.experts_held,
                cfg.initializer_range, cfg.routed_scaling_factor,
                scoring="sigmoid")
            self.shared = GatedMLP(
                cfg, cfg.num_shared_experts * cfg.moe_intermediate_size,
                "shared_mlp")
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


def delta_rule_rows(mixer, u, rows, valid, conv_s, ssm_s, fresh,
                    scope: str = "kda", state_impl: str = "xla"):
    """A delta-rule layer (``mixer``: ``gates``, ``qkv_proj``,
    ``conv_weight``, ``qkv``; this model's and ``models/olmo_hybrid.py``'s)
    over ragged rows: the first ``rows.n_chunk`` packed prompt rows through
    the chunk form (each sequence present carries its state row in and out
    once), the others one token a sequence through the step (row ``i`` of
    them on state row ``i``), under the scopes ``<scope>_chunk`` /
    ``<scope>_step``. ``state_impl`` (``CacheView.state_impl``) chooses
    the step: ``"pallas"`` the kernel over the layer's whole array in
    place, the live rows' tiles alone crossing HBM; else ``kda_step`` over
    the slots' rows, sliced out and written back. The chunk form is
    ``kda_chunk_gathered`` under either. ``(o [T, heads, V] float32,
    conv_state, ssm_state)``."""
    c = rows.n_chunk
    n_dec = u.shape[0] - c
    log_a, b = mixer.gates(u, valid)
    with jax.named_scope("conv"):
        qkv = mixer.qkv_proj(u)
    no_bias = jnp.zeros((), jnp.float32)
    os = []
    if c:
        with jax.named_scope("conv"):
            tail = jnp.where(fresh[:, None, None], 0,
                             conv_s[rows.seg_rows])
            conv, tail = ssd.causal_conv_chunk(
                qkv[:c], mixer.conv_weight, no_bias, tail,
                rows.chunk_seg)
            # (the write waits for the read: see the decode rows below)
            conv, tail = jax.lax.optimization_barrier((conv, tail))
            conv_s = conv_s.at[rows.seg_rows].set(tail)
        with jax.named_scope(scope + "_chunk"):
            o, ssm_s = kda.kda_chunk_gathered(
                *mixer.qkv(conv), log_a[:c], b[:c], ssm_s,
                rows.chunk_seg, rows.seg_rows, fresh)
            os.append(o)
    if n_dec:
        live = valid[c:]
        first = rows.positions[c:] == 0
        with jax.named_scope("conv"):
            old = conv_s[:n_dec]
            conv, tail = ssd.causal_conv_step(
                qkv[c:], mixer.conv_weight, no_bias,
                jnp.where(first[:, None, None], 0, old))
            # the new tails go over ``old`` in place, and not before the
            # convolution has read it: XLA was seen to hoist the write over
            # a rematerialised read of the donated rows (PERF.md, PR 48)
            conv, tail = jax.lax.optimization_barrier((conv, tail))
            conv_s = conv_s.at[:n_dec].set(
                jnp.where(live[:, None, None], tail, old))
        with jax.named_scope(scope + "_step"):
            if state_impl == "pallas":
                o, ssm_s = kda.kda_step_kernel(
                    *mixer.qkv(conv), log_a[c:], b[c:], ssm_s, live, first)
            else:
                # a row that is not live has log_a 0 and b 0: its state
                # row is written back as it was
                o, new = kda.kda_step(
                    *mixer.qkv(conv), log_a[c:], b[c:],
                    jnp.where((first & live)[:, None, None, None], 0.0,
                              ssm_s[:n_dec]))
                ssm_s = ssm_s.at[:n_dec].set(new)
            os.append(o)
    return (os[0] if len(os) == 1 else jnp.concatenate(os)), \
        conv_s, ssm_s


class KimiLinearForCausalLM(Layer):
    """The decoder with its untied head."""

    def __init__(self, cfg: KimiLinearConfig):
        super().__init__()
        self.cfg = cfg
        init = I.Normal(0.0, cfg.initializer_range)
        self.embed = nn.Embedding(cfg.vocab_size, cfg.hidden_size,
                                  weight_attr=init)
        self.layers = LayerList([KimiLinearLayer(cfg, i)
                                 for i in range(cfg.num_hidden_layers)])
        self.final_norm = nn.RMSNorm(cfg.hidden_size, cfg.rms_norm_eps)
        self.final_norm._scope = "ln"
        self.lm_head = _linear(cfg, cfg.hidden_size, cfg.vocab_size)

    # -- shared pieces ---------------------------------------------------
    @property
    def _dtype(self):
        """The type the matrix products run in: the weights'."""
        return self.embed.weight.dtype

    @property
    def experts_held(self) -> Tuple[int, int]:
        """``(first, count)`` of the routed experts held here."""
        return self.cfg.experts_held or (0, self.cfg.num_experts)

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
        x = self._embed(tokens)
        for layer in self.layers:
            x = x + layer.mixer(layer.input_norm(x).astype(self._dtype))
            x, _ = layer.feed_forward(x, self._dtype)
        return self.ragged_logits(x)

    def forward(self, input_ids):
        """``input_ids`` [B, S] -> logits [B, S, V]; no cache, MLA
        expanded, the delta rule in its chunk form from a zero state."""
        return jnp.stack([self._sequence(row) for row in input_ids])

    def generate(self, input_ids, max_new_tokens: int = 20):
        """Greedy decoding by the whole-sequence forward
        (:func:`~paddle_tpu.models.generation.greedy_by_forward`). The
        serving path is ``LLMEngine``; this is what it is held to."""
        return greedy_by_forward(self, input_ids, max_new_tokens)

    # -- the engine's forward over ragged rows ---------------------------
    def kv_cache_spec(self):
        """A LIST of ONE cache group (``inference/page_pool.py``), a
        LATENT one: a group without a V and without a head axis. A token's
        page row is ``[c | r]`` padded to ``latent_width``; its first
        ``kv_lora_rank`` columns are the value every head sums."""
        cfg = self.cfg
        return [CacheGroup("latent", len(cfg.layers_of("mla")), 1,
                           cfg.latent_width, None, cfg.kv_lora_rank)]

    def state_cache_spec(self):
        """The recurrent state ONE sequence holds, whatever its length:
        per KDA layer a ``conv_state`` row (the last ``kernel - 1`` inputs
        of the convolution over q, k and v, in the activations' type) and
        an ``ssm_state`` row (the delta rule's ``[heads, d, d]`` state,
        float32). ``impls``: what an engine may choose for the step:
        ``kda_step`` (``"xla"``) or, on a TPU, the kernel ``ops/kda.py
        kda_step_kernel`` over the layer's whole array in place
        (``"pallas"``: :func:`delta_rule_rows` takes it under that
        ``state_impl``). ``chunk_impls``: the chunk form exists in plain
        ``jax.numpy`` alone, so a chunk gathers and scatters as many rows
        as it may hold sequences under either. None for a stack without a
        KDA layer."""
        cfg = self.cfg
        n = len(cfg.layers_of("kda"))
        if not n:
            return None
        return {"layers": n,
                "conv_state": (cfg.conv_kernel - 1, 3 * cfg.kda_inner),
                "ssm_state": (cfg.kda_heads, cfg.kda_head_dim,
                              cfg.kda_head_dim),
                "conv_dtype": self._dtype,
                "max_chunk_sequences": MAX_CHUNK_SEQUENCES,
                "impls": ("xla", "pallas"),
                "chunk_impls": ("xla",)}

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
        padded or inactive row, whose latent row lands on scratch page 0)
        and ``tables``, a tuple with the latent group's ``[T, pages]``
        table; the first ``n_chunk`` rows are packed prompt rows
        (``chunk_seg`` / ``seg_rows`` as ``models/granite_hybrid.py``).
        ``cache``: ``k_pages`` (a tuple of ONE store ``[mla layers, pages,
        page_size, latent_width]``), ``v_pages`` ``(None,)``: there is no
        V, ``conv_state`` / ``ssm_state`` (a tuple, one ``[slots + 1,
        ...]`` array a KDA layer), ``attention_impl``, ``moe_impl``. A
        sequence's state is reset where its position is 0. Returns
        ``(hidden [T, H], cache, aux)``."""
        cfg = self.cfg
        positions, limits = rows.positions, rows.limits
        valid = limits > 0
        c = rows.n_chunk
        pool = cache.k_pages[0]
        ps = pool.shape[2]
        table = jnp.clip(rows.tables[0], 0)
        page_idx = jnp.where(valid, jnp.take_along_axis(
            table, (positions // ps)[:, None], axis=1)[:, 0], 0)
        offs = positions % ps
        conv_state = list(cache.conv_state or ())
        ssm_state = list(cache.ssm_state or ())
        fresh = None
        if c and ssm_state:
            g = rows.seg_rows.shape[0]
            oh = rows.chunk_seg[:, None] == jnp.arange(g)[None, :]
            fresh = jnp.any(oh & (positions[:c] == 0)[:, None], axis=0)
        x = self._embed(rows.tokens)
        aux = []
        i_kv = i_st = 0
        for layer in self.layers:
            u = layer.input_norm(x).astype(self._dtype)
            mixer = layer.mixer
            if layer.kind == "mla":
                with jax.named_scope("mla"):
                    q = mixer.absorb(mixer.queries(u))
                    row = jnp.pad(mixer.latent(u), (
                        (0, 0), (0, cfg.latent_width - cfg.latent_dim)))
                pool = kv_write(pool, i_kv, page_idx, offs, row)
                att = ragged_paged_attention(
                    q, pool, None, table, limits,
                    scale=mixer.scale, impl=cache.attention_impl,
                    layer=i_kv, n_chunk=c, value_dim=cfg.kv_lora_rank)
                with jax.named_scope("mla"):
                    out = mixer.project(att)
                i_kv += 1
            else:
                with jax.named_scope("kda"):
                    o, conv_state[i_st], ssm_state[i_st] = delta_rule_rows(
                        mixer, u, rows, valid, conv_state[i_st],
                        ssm_state[i_st], fresh,
                        state_impl=cache.state_impl)
                    out = mixer.finish(o, u)
                i_st += 1
            x = x + out
            x, rows_held = layer.feed_forward(x, self._dtype, valid,
                                              cache.moe_impl)
            if rows_held is not None:
                aux.append(rows_held)
        cache = cache._replace(k_pages=(pool,))
        if ssm_state:
            cache = cache._replace(conv_state=tuple(conv_state),
                                   ssm_state=tuple(ssm_state))
        if not aux:
            return x, cache, None
        pairs = jnp.sum(valid).astype(jnp.int32) * cfg.num_experts_per_token
        return x, cache, jnp.concatenate(
            [jnp.stack(aux), jnp.full((len(aux), 1), pairs, jnp.int32)], 1)
