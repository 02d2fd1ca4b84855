"""Olmo Hybrid (``model_type: olmo_hybrid``): a dense decoder whose layers
are gated-delta-rule mixers (``linear_attention``: a delta-rule state with ONE
decay a head) with a full-attention mixer every fourth layer
(``layer_types``), each followed by a SwiGLU. No rotary anywhere
(``rope_parameters.rope_theta`` null): the attention layers carry no
position, the recurrent layers do.

The equations, with ``x`` the residual stream ``[T, H]`` (float32), no
biases, ``eps`` = ``rms_norm_eps``, ``h`` one of the heads::

    kind(l) = layer_types[l]

    linear_attention (Yang et al., arXiv:2412.06464, as the linear_* keys
    lay it out; K = linear_key_head_dim, V = linear_value_head_dim):
      [q | k | v] = silu(conv(x W_qkv))     depthwise, causal, kernel
                                            linear_conv_kernel_dim, no bias
      q_h = l2norm(q_h) / sqrt(K)   k_h = l2norm(k_h)
      b_h = 2 sigmoid(x W_b)_h   in (0, 2)       (linear_allow_neg_eigval;
                                                  sigmoid alone when false)
      log a_h = -exp(A_log_h) softplus((x W_a)_h + dt_bias_h)    ONE a head
      S_t = a_t (I - b_t k_t k_t^T) S_{t-1} + b_t k_t v_t^T    S in R^{K x V}
      o_t = S_t^T q_t
      m   = concat_h(RMSNorm_V(o_h; w) * silu((x W_g)_h)) W_o

    full_attention:
      q = RMSNorm(x W_q)   k = RMSNorm(x W_k)   v = x W_v    the norms over
      the WHOLE projection (heads x head_dim wide), then heads of head_dim
      s_ij = q_i . k_j / sqrt(head_dim), j <= i;
      m = concat_h(softmax(s) v) W_o

    block:  x = x + RMSNorm(m(x));   x = x + RMSNorm(W_down(silu(W_gate x)
                                                          * W_up x))
    final RMSNorm; untied head.

In ``ops/kda.py``'s orientation the rule is ``S_t = (I - b k k^T) Diag(a)
S_{t-1} + b k v^T`` with ``a`` the same in every channel: ``log_a`` goes
down as ``[T, heads, 1]`` and the chunk form takes its scalar-decay pair
products.

ASSUMED (the published ``config.json`` does not say; each a one-line
change, the same in ``benchmark/reference/olmo_hybrid.py``): the block's
order (no norm before a branch, one on its output: the Olmo 2 / 3 order)
and the Q/K norms over the whole projection; ``rope_theta`` null read as no
rotation; the rule's layout as the ``linear_*`` keys and the gated delta
rule's reference layer give it (one convolution over q, k and v without
bias; silu; L2 norm then ``1 / sqrt(K)``; ``A_log`` a head from log U(1,
16), ``dt_bias`` the inverse softplus of a log-uniform step in (0.001,
0.1); the output gate's silu and the head norm's weight of ``V``); matrices
normal std 0.02, norms 1.

Departures, all noted: q, k and v of either mixer are one fused matrix, as
are the gate and up projections of the SwiGLU (the same numbers). WHAT IS
STORED: a rule's state row is ``[heads, K, V padded to whole lanes of
128]`` float32 (192 -> 256: the TPU tiles an array's last axis to 128
whatever its logical width, so the padding costs no byte that was not
already there, and ``state_cache_spec()`` can say what a slot really
holds); ``v`` goes into the rule padded with zeros, the state's padded
columns stay zero (``w = b (v - S^T k)``) and ``o`` is cut back. A page of
the ``full`` group keeps its heads in whole sublane tiles, thirty as 32
(``stored_kv_heads``; Mosaic refuses a 30-row slice of a page,
``tests/test_chip_compile.py``, and HBM tiles the axis so anyway):
``kv_cache_spec()`` names the 32, q, k and v reach the pool and the attention
op with zero heads behind the model's (:meth:`FullAttention.stored`) and the
op's output is cut back. Both paddings are this module's alone. Precision: the residual stream is float32 whatever the
weights' type; a branch's input is cast to the weights' type for the
product that follows; float32 for the norms' statistics, the convolution's
sum, q / k / v of the rule, the decay (log space), ``b``, the state, the
output gate, softmax and the logits.

Serving: :meth:`OlmoHybridForCausalLM.ragged_forward`. ``kv_cache_spec()``
names ONE cache group (``full``); ``state_cache_spec()`` one ``conv_state``
/ ``ssm_state`` row a slot for every ``linear_attention`` layer.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from .. import nn
from ..inference.page_pool import CacheGroup
from ..nn import functional as F
from ..nn import initializer as I
from ..nn.layer import Layer, LayerList
from ..ops import kda, ssd
from ..ops.paged_attention import (kv_page_size, kv_write,
                                   ragged_paged_attention)
from .generation import greedy_by_forward
from .kimi_linear import (_f32_product, _linear, a_log_init,
                          delta_rule_rows, dt_bias_init)
from .laguna import GatedMLP

# sequences one packed run of prompt rows may hold: the chunk form gathers
# this many carried states (ops/kda.py), so the engine packs no more
MAX_CHUNK_SEQUENCES = 8
_LANES, _SUBLANES = 128, 8
LINEAR, FULL = "linear_attention", "full_attention"
PERIOD = (LINEAR, LINEAR, LINEAR, FULL)


@dataclass
class OlmoHybridConfig:
    """The published keys of ``config.json`` under their own names, plus
    ``num_layers``: how many of the ``num_hidden_layers`` published layers
    are built here, the first ones (None = all)."""
    vocab_size: int = 100352
    hidden_size: int = 3840
    intermediate_size: int = 11008
    num_hidden_layers: int = 32
    num_attention_heads: int = 30
    num_key_value_heads: int = 30
    hidden_act: str = "silu"
    max_position_embeddings: int = 65536
    attention_bias: bool = False
    rms_norm_eps: float = 1e-6
    tie_word_embeddings: bool = False
    layer_types: Tuple[str, ...] = field(
        default_factory=lambda: PERIOD * 8)
    linear_num_key_heads: int = 30
    linear_num_value_heads: int = 30
    linear_key_head_dim: int = 96
    linear_value_head_dim: int = 192
    linear_conv_kernel_dim: int = 4
    linear_allow_neg_eigval: bool = True
    rope_parameters: dict = field(
        default_factory=lambda: {"rope_theta": None})
    initializer_range: float = 0.02
    num_layers: Optional[int] = None

    def __post_init__(self):
        if self.num_layers is None:
            self.num_layers = self.num_hidden_layers
        self.layer_types = tuple(self.layer_types)
        if len(self.layer_types) < self.num_layers or any(
                t not in (LINEAR, FULL) for t in self.layer_types):
            raise ValueError("layer_types names a kind, linear_attention "
                             "or full_attention, for every layer built")
        if (self.rope_parameters or {}).get("rope_theta") is not None:
            raise NotImplementedError(
                "rope_theta: the published value is null (no rotation)")
        if self.linear_num_key_heads != self.linear_num_value_heads:
            raise NotImplementedError(
                "linear_num_key_heads != linear_num_value_heads: the "
                "published rule has a key head a value head")
        if self.attention_bias or self.tie_word_embeddings \
                or self.hidden_act != "silu":
            raise NotImplementedError(
                "published: no attention bias, an untied head, silu")
        if self.hidden_size % self.num_attention_heads \
                or self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("heads must divide the hidden size and K/V "
                             "heads the heads")

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def layer_kinds(self) -> Tuple[str, ...]:
        return self.layer_types[:self.num_layers]

    def layers_of(self, kind: str) -> Tuple[int, ...]:
        return tuple(i for i, t in enumerate(self.layer_kinds) if t == kind)

    @property
    def key_inner(self) -> int:
        return self.linear_num_key_heads * self.linear_key_head_dim

    @property
    def value_inner(self) -> int:
        return self.linear_num_value_heads * self.linear_value_head_dim

    @property
    def conv_width(self) -> int:
        """The channels of the one convolution over ``[q | k | v]``."""
        return 2 * self.key_inner + self.value_inner

    @property
    def value_width(self) -> int:
        """A head's value AS THE STATE STORES IT: whole lanes."""
        return -(-self.linear_value_head_dim // _LANES) * _LANES

    @property
    def stored_kv_heads(self) -> int:
        """The K/V heads AS A PAGE STORES THEM: whole sublane tiles (1, 2
        and 4 divide one)."""
        kv = self.num_key_value_heads
        return kv if kv in (1, 2, 4) else -(-kv // _SUBLANES) * _SUBLANES


class GatedDeltaMixer(Layer):
    """The ``linear_attention`` mixer: its projections and the steps around
    the rule; the rule's state is the caller's."""

    def __init__(self, cfg: OlmoHybridConfig):
        super().__init__()
        self.cfg = cfg
        nh = cfg.linear_num_value_heads
        self.qkv_proj = _linear(cfg, cfg.hidden_size, cfg.conv_width)
        self.conv_weight = self.create_parameter(
            [cfg.linear_conv_kernel_dim, cfg.conv_width],
            initializer=I.Normal(0.0, cfg.initializer_range))
        self.a_proj = _linear(cfg, cfg.hidden_size, nh)
        self.dt_bias = self.create_parameter(
            [nh], dtype="float32", initializer=dt_bias_init)
        self.A_log = self.create_parameter(
            [nh], dtype="float32", initializer=a_log_init)
        self.b_proj = _linear(cfg, cfg.hidden_size, nh)
        self.g_proj = _linear(cfg, cfg.hidden_size, cfg.value_inner)
        self.o_norm_weight = self.create_parameter(
            [cfg.linear_value_head_dim], initializer=I.Constant(1.0))
        self.o_proj = _linear(cfg, cfg.value_inner, cfg.hidden_size)

    def gates(self, u, valid=None):
        """``(log_a [T, heads, 1], b [T, heads])`` float32: ONE decay a
        head, in log space, and the write strength, in (0, 2) where
        ``linear_allow_neg_eigval``. A row that is not ``valid`` gets 0 and
        0: it moves no state."""
        cfg = self.cfg
        with jax.named_scope("gate"):
            log_a = -jnp.exp(self.A_log.astype(jnp.float32)) \
                * jax.nn.softplus(_f32_product(u, self.a_proj.weight)
                                  + self.dt_bias.astype(jnp.float32))
            b = jax.nn.sigmoid(_f32_product(u, self.b_proj.weight))
            if cfg.linear_allow_neg_eigval:
                b = 2.0 * b
            if valid is not None:
                log_a = jnp.where(valid[:, None], log_a, 0.0)
                b = jnp.where(valid[:, None], b, 0.0)
            return log_a[..., None], b

    def qkv(self, conv_out):
        """``(q, k [T, heads, K], v [T, heads, value_width])`` float32 from
        the convolution's float32 output: silu, L2 norm of q and k, q
        scaled by ``1 / sqrt(K)``, v padded with zeros to the width the
        state stores."""
        cfg = self.cfg
        nh, dk = cfg.linear_num_key_heads, cfg.linear_key_head_dim
        q, k, v = jnp.split(jax.nn.silu(conv_out),
                            [cfg.key_inner, 2 * cfg.key_inner], axis=-1)
        v = v.reshape(-1, nh, cfg.linear_value_head_dim)
        v = jnp.pad(v, ((0, 0), (0, 0),
                        (0, cfg.value_width - cfg.linear_value_head_dim)))
        return (kda.l2norm(q.reshape(-1, nh, dk)) * dk ** -0.5,
                kda.l2norm(k.reshape(-1, nh, dk)), v)

    def finish(self, o, u):
        """``o`` [T, heads, value_width] as the rule gives it: cut to the
        published width, a head's norm, the output gate, the output
        projection."""
        cfg = self.cfg
        with jax.named_scope("gate"):
            g = jax.nn.silu(_f32_product(u, self.g_proj.weight))
            o = F.rms_norm(o[..., :cfg.linear_value_head_dim],
                           self.o_norm_weight.astype(jnp.float32),
                           cfg.rms_norm_eps)
            y = (o.reshape(-1, cfg.value_inner) * g).astype(u.dtype)
        return self.o_proj(y)

    def forward(self, u):
        """One whole sequence ``u`` [S, H] from a zero state, the chunk
        form in pieces."""
        cfg = self.cfg
        seg = jnp.zeros((u.shape[0],), jnp.int32)
        log_a, b = self.gates(u)
        with jax.named_scope("conv"):
            tail = jnp.zeros((1, cfg.linear_conv_kernel_dim - 1,
                              cfg.conv_width), u.dtype)
            conv, _ = ssd.causal_conv_chunk(
                self.qkv_proj(u), self.conv_weight,
                jnp.zeros((), jnp.float32), tail, seg)
        with jax.named_scope("gdn_chunk"):
            state = jnp.zeros((1, cfg.linear_num_value_heads,
                               cfg.linear_key_head_dim, cfg.value_width),
                              jnp.float32)
            o, _ = kda.kda_chunked(*self.qkv(conv), log_a, b, state, seg)
        return self.finish(o, u)


class FullAttention(Layer):
    """The ``full_attention`` mixer: RMSNorm over the whole q and k
    projections, no rotation."""

    def __init__(self, cfg: OlmoHybridConfig):
        super().__init__()
        self.cfg = cfg
        self.q_size = cfg.num_attention_heads * cfg.head_dim
        self.kv_size = cfg.num_key_value_heads * cfg.head_dim
        self.qkv_proj = _linear(cfg, cfg.hidden_size,
                                self.q_size + 2 * self.kv_size)
        self.q_norm = nn.RMSNorm(self.q_size, cfg.rms_norm_eps)
        self.k_norm = nn.RMSNorm(self.kv_size, cfg.rms_norm_eps)
        self.o_proj = _linear(cfg, self.q_size, cfg.hidden_size)
        self.scale = cfg.head_dim ** -0.5

    def qkv(self, u):
        """``(q [T, heads, d], k, v [T, kv_heads, d])`` in ``u``'s type."""
        cfg = self.cfg
        q, k, v = jnp.split(self.qkv_proj(u),
                            [self.q_size, self.q_size + self.kv_size], -1)
        with jax.named_scope("qk_norm"):
            q = self.q_norm(q).astype(u.dtype)
            k = self.k_norm(k).astype(u.dtype)
        return (q.reshape(-1, cfg.num_attention_heads, cfg.head_dim),
                k.reshape(-1, cfg.num_key_value_heads, cfg.head_dim),
                v.reshape(-1, cfg.num_key_value_heads, cfg.head_dim))

    def stored(self, q, k, v):
        """``q``, ``k``, ``v`` with zero heads behind the model's, up to the
        heads a page stores (``stored_kv_heads``): what the pool and the
        attention op are handed. A zero query head attends zero K/V heads;
        :meth:`project` cuts what it returns."""
        cfg = self.cfg
        extra = cfg.stored_kv_heads - cfg.num_key_value_heads
        group = cfg.num_attention_heads // cfg.num_key_value_heads
        return tuple(jnp.pad(t, ((0, 0), (0, n), (0, 0)))
                     for t, n in ((q, group * extra), (k, extra), (v, extra)))

    def project(self, att):
        """``att`` [T, heads or more, d]: the heads past the model's are the
        zero heads of :meth:`stored`."""
        att = att[:, :self.cfg.num_attention_heads]
        return self.o_proj(att.reshape(-1, self.q_size)
                           .astype(self.o_proj.weight.dtype))

    def forward(self, u):
        """One whole sequence ``u`` [S, H]: plain causal attention."""
        cfg = self.cfg
        with jax.named_scope("attn_full"):
            s = u.shape[0]
            q, k, v = (t.astype(jnp.float32) for t in self.qkv(u))
            group = cfg.num_attention_heads // cfg.num_key_value_heads
            k, v = (jnp.repeat(t, group, axis=1) for t in (k, v))
            sc = jnp.einsum("qhd,khd->hqk", q, k) * self.scale
            sc = jnp.where(jnp.tril(jnp.ones((s, s), bool)), sc, -jnp.inf)
            att = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(sc, -1), v)
            return self.project(att)


class OlmoHybridLayer(Layer):
    def __init__(self, cfg: OlmoHybridConfig, index: int):
        super().__init__()
        self.kind = cfg.layer_kinds[index]
        self.mixer = GatedDeltaMixer(cfg) if self.kind == LINEAR \
            else FullAttention(cfg)
        self.mixer_norm = nn.RMSNorm(cfg.hidden_size, cfg.rms_norm_eps)
        self.mlp = GatedMLP(cfg, cfg.intermediate_size, "mlp")
        self.mlp_norm = nn.RMSNorm(cfg.hidden_size, cfg.rms_norm_eps)
        for norm in (self.mixer_norm, self.mlp_norm):
            norm._scope = "ln"

    def feed_forward(self, x, dtype):
        """The second half of the layer on the float32 stream ``x``: the
        norm is on the branch's OUTPUT."""
        return x + self.mlp_norm(self.mlp(x.astype(dtype))
                                 .astype(jnp.float32))


class OlmoHybridForCausalLM(Layer):
    """The decoder with its untied head."""

    def __init__(self, cfg: OlmoHybridConfig):
        super().__init__()
        self.cfg = cfg
        init = I.Normal(0.0, cfg.initializer_range)
        self.embed = nn.Embedding(cfg.vocab_size, cfg.hidden_size,
                                  weight_attr=init)
        self.layers = LayerList([OlmoHybridLayer(cfg, i)
                                 for i in range(cfg.num_layers)])
        self.final_norm = nn.RMSNorm(cfg.hidden_size, cfg.rms_norm_eps)
        self.final_norm._scope = "ln"
        self.lm_head = _linear(cfg, cfg.hidden_size, cfg.vocab_size)

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
        x = self._embed(tokens)
        for layer in self.layers:
            u = x.astype(self._dtype)
            with jax.named_scope("gdn" if layer.kind == LINEAR
                                 else "attn_full"):
                out = layer.mixer(u)
            x = x + layer.mixer_norm(out.astype(jnp.float32))
            x = layer.feed_forward(x, self._dtype)
        return self.ragged_logits(x)

    def forward(self, input_ids):
        """``input_ids`` [B, S] -> logits [B, S, V]; no cache, the delta
        rule in its chunk form from a zero state, in pieces."""
        return jnp.stack([self._sequence(row) for row in input_ids])

    def generate(self, input_ids, max_new_tokens: int = 20):
        """Greedy decoding by the whole-sequence forward
        (:func:`~paddle_tpu.models.generation.greedy_by_forward`). The
        serving path is ``LLMEngine``; this is what it is held to."""
        return greedy_by_forward(self, input_ids, max_new_tokens)

    # -- the engine's forward over ragged rows ---------------------------
    def kv_cache_spec(self):
        """A LIST of ONE cache group (``inference/page_pool.py``): the
        ``full_attention`` layers' K and V, heads of ``head_dim`` AS
        STORED (``stored_kv_heads``: the published thirty as 32, the two
        behind them zeros)."""
        cfg = self.cfg
        return [CacheGroup("full", len(cfg.layers_of(FULL)),
                           cfg.stored_kv_heads, cfg.head_dim)]

    def state_cache_spec(self):
        """The recurrent state ONE sequence holds, whatever its length:
        per ``linear_attention`` layer a ``conv_state`` row (the last
        ``kernel - 1`` inputs of the convolution over q, k and v, in the
        activations' type) and an ``ssm_state`` row, the rule's state AS
        STORED: ``[heads, K, value_width]`` float32, a head's value padded
        to whole lanes. ``impls``: what an engine may choose for the
        step: ``kda_step`` or, on a TPU, the kernel ``ops/kda.py
        kda_step_kernel`` (one decay a head is a scalar a tile there);
        ``chunk_impls``: the chunk form exists in plain ``jax.numpy``
        alone. None for a stack without such a layer."""
        cfg = self.cfg
        n = len(cfg.layers_of(LINEAR))
        if not n:
            return None
        return {"layers": n,
                "conv_state": (cfg.linear_conv_kernel_dim - 1,
                               cfg.conv_width),
                "ssm_state": (cfg.linear_num_value_heads,
                              cfg.linear_key_head_dim, cfg.value_width),
                "conv_dtype": self._dtype,
                "max_chunk_sequences": MAX_CHUNK_SEQUENCES,
                "impls": ("xla", "pallas"),
                "chunk_impls": ("xla",),
                "rule": "delta, scalar decay"}

    def moe_aux_spec(self):
        return None

    def loop_aux_spec(self):
        return None

    def ragged_forward(self, rows, cache):
        """``rows``: ``tokens``, ``positions``, ``limits`` [T] (0 = a
        padded or inactive row, whose K/V lands on scratch page 0) and
        ``tables``, a tuple with the ``full`` group's ``[T, pages]``
        table; the first ``n_chunk`` rows are packed prompt rows
        (``chunk_seg`` / ``seg_rows`` as ``models/granite_hybrid.py``).
        ``cache``: ``k_pages`` / ``v_pages`` (a tuple of ONE stacked pool
        each, ``[full layers, pages, page_size, stored heads, head_dim]``),
        ``conv_state`` / ``ssm_state`` (a tuple, one ``[slots + 1, ...]``
        array a ``linear_attention`` layer), ``attention_impl``. A
        sequence's state is reset where its position is 0. Returns
        ``(hidden [T, H], cache, None)``."""
        positions, limits = rows.positions, rows.limits
        valid = limits > 0
        c = rows.n_chunk
        k_pool, v_pool = cache.k_pages[0], cache.v_pages[0]
        ps = kv_page_size(k_pool)
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
        i_kv = i_st = 0
        for layer in self.layers:
            u = x.astype(self._dtype)
            mixer = layer.mixer
            if layer.kind == FULL:
                with jax.named_scope("attn_full"):
                    q, k, v = mixer.stored(*mixer.qkv(u))
                    k_pool = kv_write(k_pool, i_kv, page_idx, offs, k)
                    v_pool = kv_write(v_pool, i_kv, page_idx, offs, v)
                    att = ragged_paged_attention(
                        q, k_pool, v_pool, table, limits,
                        scale=mixer.scale, impl=cache.attention_impl,
                        layer=i_kv, n_chunk=c)
                    out = mixer.project(att)
                i_kv += 1
            else:
                with jax.named_scope("gdn"):
                    # (the chunk form for the packed prompt rows, the
                    # step for the others: the per-channel model's walk)
                    o, conv_state[i_st], ssm_state[i_st] = delta_rule_rows(
                        mixer, u, rows, valid, conv_state[i_st],
                        ssm_state[i_st], fresh, scope="gdn",
                        state_impl=cache.state_impl)
                    out = mixer.finish(o, u)
                i_st += 1
            x = x + layer.mixer_norm(out.astype(jnp.float32))
            x = layer.feed_forward(x, self._dtype)
        cache = cache._replace(k_pages=(k_pool,), v_pages=(v_pool,))
        if ssm_state:
            cache = cache._replace(conv_state=tuple(conv_state),
                                   ssm_state=tuple(ssm_state))
        return x, cache, None
