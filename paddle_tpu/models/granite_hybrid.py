"""Granite 4.0-H (``model_type: granitemoehybrid``): a hybrid decoder whose
layers are Mamba-2 state-space mixers with a GQA attention mixer every few
layers (``layer_types``), each followed by routed + shared experts.

The equations, with ``h`` the residual stream, per layer ``l``::

    u = RMSNorm_in(h);    h = h + r * Mixer_l(u)
    v = RMSNorm_post(h);  h = h + r * (Routed(v) + Shared(v))

``r = residual_multiplier``; ``h0 = embedding_multiplier * E[ids]``;
``logits = RMSNorm_f(h) @ E^T / logits_scaling`` (tied head, over the rows of
``E`` held here); every RMSNorm with ``rms_norm_eps``.

- *Attention mixer*: ``q, k, v, o`` without bias, ``num_attention_heads`` /
  ``num_key_value_heads`` heads of ``hidden / heads``, NO positional
  encoding (``position_embedding_type: nope``), scores scaled by
  ``attention_multiplier`` (not ``1 / sqrt(d)``), causal.
- *Mamba-2 mixer* (``d_inner = mamba_n_heads * mamba_d_head``, state ``N``,
  one group): ``[z | xBC | dt] = in_proj(u)``; ``xBC = silu(conv1d(xBC) +
  b)`` depthwise and causal over the last ``mamba_d_conv`` positions;
  ``[x | B | C] = xBC``; ``dt = softplus(dt + dt_bias)``; ``A = -exp(A_log)``
  a head; per head ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T``, ``y_t =
  S_t C_t + D x_t`` (ops/ssd.py); ``y = RMSNorm(y * silu(z))`` over all of
  ``d_inner``; ``out = out_proj(y)``.
- *Experts* (nn/layers/dropless_moe.py): router logits over all
  ``num_local_experts`` in float32, the ``num_experts_per_tok`` largest,
  gates = softmax over those; expert ``W_out (silu(a) * b)``, ``[a | b] =
  W_in v``; ``Routed`` sums the chosen experts THAT ARE HELD HERE
  (``experts_held``), no token is dropped. ``Shared`` has the same form at
  ``shared_intermediate_size``, for every row.

Departures from the published implementation, all noted: the q, k, v
projections are one fused matrix; ``time_step_limit`` is (0, inf) (no clamp);
float32 for the router product, the gate softmax, the decays, their
cumulative sums and the SSM state, the activations' type elsewhere;
``mamba_n_groups`` must be 1 (the published value).

Serving: :meth:`GraniteHybridForCausalLM.ragged_forward` is the forward over
ragged rows that ``LLMEngine`` calls (the model owns the walk, the engine the
cache view): K/V pages for the attention layers, one ``conv_state`` /
``ssm_state`` row a slot for the Mamba-2 layers.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from .. import nn
from ..nn import functional as F
from ..nn import initializer as I
from ..nn.layer import Layer, LayerList
from ..nn.layers.dropless_moe import DroplessMoE
from ..ops import ssd
from ..ops.paged_attention import (kv_page_size, kv_write,
                                   ragged_paged_attention)
from .generation import greedy_by_forward

# sequences one packed run of prompt rows may hold: the scan gathers this
# many carried states (ops/ssd.py), so the engine packs no more into a chunk
MAX_CHUNK_SEQUENCES = 8


@dataclass
class GraniteHybridConfig:
    """The published keys of ``config.json`` under their own names, plus
    ``experts_held`` (which routed experts this chip holds; None = all).
    ``layer_types`` is given as a list, so any cut of depth keeps the
    pattern; ``vocab_size`` is the number of rows of the (tied) embedding
    held here."""
    vocab_size: int = 100352
    hidden_size: int = 4096
    layer_types: Sequence[str] = ("mamba",) * 5 + ("attention",) \
        + ("mamba",) * 4
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    attention_multiplier: float = 0.0078125
    embedding_multiplier: float = 12.0
    residual_multiplier: float = 0.22
    logits_scaling: float = 16.0
    rms_norm_eps: float = 1e-5
    intermediate_size: int = 768
    shared_intermediate_size: int = 1536
    num_local_experts: int = 72
    num_experts_per_tok: int = 10
    mamba_n_heads: int = 128
    mamba_d_head: int = 64
    mamba_d_state: int = 128
    mamba_d_conv: int = 4
    mamba_n_groups: int = 1
    mamba_chunk_size: int = 256
    max_position_embeddings: int = 131072
    initializer_range: float = 0.02
    experts_held: Optional[Tuple[int, int]] = None

    def __post_init__(self):
        self.layer_types = tuple(self.layer_types)
        bad = set(self.layer_types) - {"mamba", "attention"}
        if bad:
            raise ValueError(f"unknown layer types {sorted(bad)}")
        if self.mamba_n_groups != 1:
            raise NotImplementedError(
                "mamba_n_groups != 1: B and C are shared by every head")
        if self.experts_held is not None:
            self.experts_held = tuple(int(v) for v in self.experts_held)

    # what the engine asks of any model's configuration
    @property
    def num_layers(self) -> int:
        return len(self.layer_types)

    @property
    def num_heads(self) -> int:
        return self.num_attention_heads

    @property
    def num_kv_heads(self) -> int:
        return self.num_key_value_heads

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def d_inner(self) -> int:
        return self.mamba_n_heads * self.mamba_d_head

    @property
    def conv_dim(self) -> int:
        return self.d_inner + 2 * self.mamba_n_groups * self.mamba_d_state

    @property
    def kv_layers(self) -> Tuple[int, ...]:
        return tuple(i for i, t in enumerate(self.layer_types)
                     if t == "attention")

    @property
    def state_layers(self) -> Tuple[int, ...]:
        return tuple(i for i, t in enumerate(self.layer_types)
                     if t == "mamba")


def _linear(cfg, n_in, n_out):
    return nn.Linear(n_in, n_out, bias_attr=False,
                     weight_attr=I.Normal(0.0, cfg.initializer_range))


class GraniteAttention(Layer):
    def __init__(self, cfg: GraniteHybridConfig):
        super().__init__()
        self.cfg = cfg
        kv = cfg.num_kv_heads * cfg.head_dim
        self.qkv_proj = _linear(cfg, cfg.hidden_size,
                                cfg.hidden_size + 2 * kv)
        self.o_proj = _linear(cfg, cfg.hidden_size, cfg.hidden_size)

    def qkv(self, u):
        cfg = self.cfg
        t = u.shape[0]
        kv = cfg.num_kv_heads * cfg.head_dim
        q, k, v = jnp.split(self.qkv_proj(u),
                            [cfg.hidden_size, cfg.hidden_size + kv], -1)
        return (q.reshape(t, cfg.num_heads, cfg.head_dim),
                k.reshape(t, cfg.num_kv_heads, cfg.head_dim),
                v.reshape(t, cfg.num_kv_heads, cfg.head_dim))

    def forward(self, u):
        """One whole sequence ``u`` [S, H]: plain causal attention."""
        cfg = self.cfg
        with jax.named_scope("attn"):
            q, k, v = self.qkv(u)
            s = u.shape[0]
            rep = cfg.num_heads // cfg.num_kv_heads
            k, v = jnp.repeat(k, rep, 1), jnp.repeat(v, rep, 1)
            sc = jnp.einsum("qhd,khd->hqk", q.astype(jnp.float32),
                            k.astype(jnp.float32)) \
                * cfg.attention_multiplier
            sc = jnp.where(jnp.tril(jnp.ones((s, s), bool)), sc, -jnp.inf)
            a = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(sc, -1),
                           v.astype(jnp.float32)).astype(u.dtype)
            return self.o_proj(a.reshape(s, cfg.hidden_size))


class Mamba2Mixer(Layer):
    def __init__(self, cfg: GraniteHybridConfig):
        super().__init__()
        self.cfg = cfg
        nh, k = cfg.mamba_n_heads, cfg.mamba_d_conv
        self.in_proj = _linear(cfg, cfg.hidden_size,
                               cfg.d_inner + cfg.conv_dim + nh)
        # the state-space reference implementation's initialisers:
        # Conv1d's uniform(+-1/sqrt(k)); dt = exp(U(log 1e-3, log 1e-1))
        # through the inverse softplus; A ~ U(1, 16); D = 1
        bound = k ** -0.5
        self.conv_weight = self.create_parameter(
            [k, cfg.conv_dim], initializer=I.Uniform(-bound, bound))
        self.conv_bias = self.create_parameter(
            [cfg.conv_dim], initializer=I.Uniform(-bound, bound))

        def dt_bias(shape, dtype):
            dt = jnp.exp(I.Uniform(math.log(1e-3), math.log(1e-1))(
                shape, jnp.float32))
            return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)

        def a_log(shape, dtype):
            return jnp.log(I.Uniform(1.0, 16.0)(shape, jnp.float32)) \
                .astype(dtype)

        self.dt_bias = self.create_parameter([nh], initializer=dt_bias)
        self.A_log = self.create_parameter([nh], initializer=a_log)
        self.D = self.create_parameter([nh], initializer=I.Constant(1.0))
        self.norm_weight = self.create_parameter(
            [cfg.d_inner], initializer=I.Constant(1.0))
        self.out_proj = _linear(cfg, cfg.d_inner, cfg.hidden_size)

    def split(self, u):
        cfg = self.cfg
        z, xbc, dt = jnp.split(
            self.in_proj(u), [cfg.d_inner, cfg.d_inner + cfg.conv_dim], -1)
        dt = jax.nn.softplus(dt.astype(jnp.float32)
                             + self.dt_bias.astype(jnp.float32))
        return z, xbc, dt

    def scan_inputs(self, conv_out, dtype):
        """``(x [T, heads, d_head], B [T, N], C [T, N])`` from the
        convolution's float32 output."""
        cfg = self.cfg
        xbc = jax.nn.silu(conv_out).astype(dtype)
        x, b, c = jnp.split(
            xbc, [cfg.d_inner, cfg.d_inner + cfg.mamba_d_state], -1)
        return (x.reshape(-1, cfg.mamba_n_heads, cfg.mamba_d_head), b, c)

    @property
    def A(self):
        return -jnp.exp(self.A_log.astype(jnp.float32))

    def finish(self, y, z):
        """Gate, norm over all of ``d_inner``, output projection."""
        cfg = self.cfg
        y = y.reshape(-1, cfg.d_inner) * jax.nn.silu(z.astype(jnp.float32))
        y = F.rms_norm(y, self.norm_weight.astype(jnp.float32),
                       cfg.rms_norm_eps)
        return self.out_proj(y.astype(z.dtype))

    def forward(self, u):
        """One whole sequence ``u`` [S, H] from a zero state."""
        cfg = self.cfg
        s = u.shape[0]
        seg = jnp.zeros((s,), jnp.int32)
        z, xbc, dt = self.split(u)
        with jax.named_scope("conv"):
            tail = jnp.zeros((1, cfg.mamba_d_conv - 1, cfg.conv_dim),
                             xbc.dtype)
            conv, _ = ssd.causal_conv_chunk(xbc, self.conv_weight,
                                            self.conv_bias, tail, seg)
        with jax.named_scope("ssm"):
            x, b, c = self.scan_inputs(conv, u.dtype)
            state = jnp.zeros((1, cfg.mamba_n_heads, cfg.mamba_d_head,
                               cfg.mamba_d_state), jnp.float32)
            y, _ = ssd.ssd_chunked(x, dt, self.A, b, c, self.D, state, seg,
                                   cfg.mamba_chunk_size)
            return self.finish(y, z)


class GatedMLP(Layer):
    """``W_out (silu(a) * b)``, ``[a | b] = W_in x``: the shared expert."""

    def __init__(self, cfg: GraniteHybridConfig, width: int):
        super().__init__()
        self.w_in = _linear(cfg, cfg.hidden_size, 2 * width)
        self.w_out = _linear(cfg, width, cfg.hidden_size)
        self._scope = "shared_mlp"

    def forward(self, x):
        a, b = jnp.split(self.w_in(x), 2, -1)
        return self.w_out(jax.nn.silu(a) * b)


class GraniteHybridLayer(Layer):
    def __init__(self, cfg: GraniteHybridConfig, kind: str):
        super().__init__()
        self.kind = kind
        self.input_norm = nn.RMSNorm(cfg.hidden_size, cfg.rms_norm_eps)
        self.mixer = GraniteAttention(cfg) if kind == "attention" \
            else Mamba2Mixer(cfg)
        self.post_norm = nn.RMSNorm(cfg.hidden_size, cfg.rms_norm_eps)
        self.moe = DroplessMoE(
            cfg.hidden_size, cfg.intermediate_size, cfg.num_local_experts,
            cfg.num_experts_per_tok, cfg.experts_held,
            cfg.initializer_range)
        self.shared = GatedMLP(cfg, cfg.shared_intermediate_size)
        for norm in (self.input_norm, self.post_norm):
            norm._scope = "ln"

    def experts(self, x, r, valid=None, moe_impl: str = "xla"):
        """The second half of the layer; ``(h, rows each held expert
        received)``."""
        v = self.post_norm(x)
        routed, rows_held = self.moe(v, valid, moe_impl)
        return x + r * (routed + self.shared(v)), rows_held


class GraniteHybridForCausalLM(Layer):
    """The hybrid decoder with its tied, scaled head."""

    def __init__(self, cfg: GraniteHybridConfig):
        super().__init__()
        self.cfg = cfg
        # the scaled embedding enters the stream at the other matrices'
        # scale (times 12 at their std, the tied head would answer every
        # position with its own input token)
        self.embed = nn.Embedding(
            cfg.vocab_size, cfg.hidden_size,
            weight_attr=I.Normal(0.0, cfg.initializer_range
                                 / cfg.embedding_multiplier))
        self.layers = LayerList([GraniteHybridLayer(cfg, kind)
                                 for kind in cfg.layer_types])
        self.final_norm = nn.RMSNorm(cfg.hidden_size, cfg.rms_norm_eps)
        self.final_norm._scope = "ln"

    # -- shared pieces ---------------------------------------------------
    def _embed(self, tokens):
        with jax.named_scope("embed"):
            e = self.embed(tokens)
            return e * jnp.asarray(self.cfg.embedding_multiplier, e.dtype)

    def ragged_logits(self, hidden):
        """``hidden`` [R, H] (before the final norm) -> logits [R, V]."""
        x = self.final_norm(hidden)
        with jax.named_scope("lm_head"):
            w = self.embed.weight
            return jnp.einsum("rh,vh->rv", x, w.astype(x.dtype)) \
                / self.cfg.logits_scaling

    # -- whole sequences (tests, generate) -------------------------------
    def _sequence(self, tokens):
        cfg = self.cfg
        x = self._embed(tokens)
        for layer in self.layers:
            x = x + cfg.residual_multiplier * layer.mixer(
                layer.input_norm(x))
            x, _ = layer.experts(x, cfg.residual_multiplier)
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
        """``(layers, kv_heads, head_dim)`` of the paged K/V pool: a bare
        triple, one cache group of K and V pages
        (``inference/page_pool.py``; a group without a V is a
        ``CacheGroup`` with ``value_dim``)."""
        cfg = self.cfg
        return len(cfg.kv_layers), cfg.num_kv_heads, cfg.head_dim

    def state_cache_spec(self):
        """The recurrent state ONE sequence holds, whatever its length:
        per state-space layer a ``conv_state`` row (the last ``d_conv - 1``
        inputs of the convolution, in the activations' type) and an
        ``ssm_state`` row (float32: a recurrence rounds at every token)."""
        cfg = self.cfg
        return {"layers": len(cfg.state_layers),
                "conv_state": (cfg.mamba_d_conv - 1, cfg.conv_dim),
                "ssm_state": (cfg.mamba_n_heads, cfg.mamba_d_head,
                              cfg.mamba_d_state),
                "conv_dtype": self.embed.weight.dtype,
                "max_chunk_sequences": MAX_CHUNK_SEQUENCES}

    def moe_aux_spec(self):
        """``(layers, held experts)``: :meth:`ragged_forward`'s ``aux`` is
        int32 ``[layers, held + 1]``, the rows each held expert received
        and, last, every (row, expert) pair the router made."""
        return self.cfg.num_layers, self.layers[0].moe.count

    def loop_aux_spec(self):
        """The stack runs once: ``aux`` is :meth:`moe_aux_spec`'s."""
        return None

    def ragged_forward(self, rows, cache):
        """``rows``: ``tokens``, ``positions``, ``limits`` [T] (0 = a
        padded or inactive row), ``tables`` [T, pages]; the first
        ``n_chunk`` rows are packed prompt rows (``chunk_seg`` [n_chunk] =
        the local index of a row's sequence, ``seg_rows`` [G] = that
        sequence's state row), the others one token a sequence, row ``i``
        of them on state row ``i``. ``cache``: ``k_pages``, ``v_pages``,
        ``conv_state``, ``ssm_state`` (a tuple, one array a state-space
        layer, ``[rows + 1, ...]``: the last row takes what padded rows
        write), ``attention_impl``, ``state_impl`` (``"pallas"``: the
        decode rows' state through ``ssd.ssd_step_kernel`` and the chunk's
        scan through ``ssd.ssd_chunk_kernel``, both over the layer's whole
        array in place; else ``ssd.ssd_step`` and
        ``ssd.ssd_chunk_gathered``), ``moe_impl`` (``"pallas"``: the routed experts'
        grouped products through ``ops/grouped_matmul.py``; else
        ``jax.lax.ragged_dot``). A sequence's state is reset where its
        position is 0. Returns ``(hidden [T, H], cache, aux)``."""
        cfg = self.cfg
        r = cfg.residual_multiplier
        tokens, positions, limits = rows.tokens, rows.positions, rows.limits
        valid = limits > 0
        c = rows.n_chunk
        n_dec = tokens.shape[0] - c
        ps = kv_page_size(cache.k_pages)
        tables = jnp.clip(rows.tables, 0)
        page_idx = jnp.take_along_axis(
            tables, (positions // ps)[:, None], axis=1)[:, 0]
        page_idx = jnp.where(valid, page_idx, 0)      # pads -> scratch 0
        offs = positions % ps
        k_pages, v_pages = cache.k_pages, cache.v_pages
        conv_state, ssm_state = list(cache.conv_state), \
            list(cache.ssm_state)
        if c:
            g = rows.seg_rows.shape[0]
            oh = rows.chunk_seg[:, None] == jnp.arange(g)[None, :]
            fresh = jnp.any(oh & (positions[:c] == 0)[:, None], axis=0)
        x = self._embed(tokens)
        aux = []
        i_kv = i_st = 0
        for layer in self.layers:
            u = layer.input_norm(x)
            mixer = layer.mixer
            if layer.kind == "attention":
                with jax.named_scope("attn"):
                    q, k, v = mixer.qkv(u)
                k_pages = kv_write(k_pages, i_kv, page_idx, offs, k)
                v_pages = kv_write(v_pages, i_kv, page_idx, offs, v)
                att = ragged_paged_attention(
                    q, k_pages, v_pages, tables, limits,
                    scale=cfg.attention_multiplier,
                    impl=cache.attention_impl, layer=i_kv,
                    n_chunk=rows.n_chunk)
                with jax.named_scope("attn"):
                    out = mixer.o_proj(
                        att.reshape(-1, cfg.hidden_size).astype(u.dtype))
                i_kv += 1
            else:
                z, xbc, dt = mixer.split(u)
                dt = jnp.where(valid[:, None], dt, 0.0)
                conv_s, ssm_s = conv_state[i_st], ssm_state[i_st]
                ys = []
                if c:
                    with jax.named_scope("conv"):
                        tail = jnp.where(fresh[:, None, None], 0,
                                         conv_s[rows.seg_rows])
                        conv, tail = ssd.causal_conv_chunk(
                            xbc[:c], mixer.conv_weight, mixer.conv_bias,
                            tail, rows.chunk_seg)
                        conv_s = conv_s.at[rows.seg_rows].set(tail)
                    with jax.named_scope("ssm"):
                        xs, b, cc = mixer.scan_inputs(conv, u.dtype)
                        # the kernel: the layer's whole array in place,
                        # the tiles of the chunk's own sequences alone
                        # cross HBM; else gather, scan, scatter
                        scan = ssd.ssd_chunk_kernel \
                            if cache.state_impl == "pallas" \
                            else ssd.ssd_chunk_gathered
                        y, ssm_s = scan(
                            xs, dt[:c], mixer.A, b, cc, mixer.D, ssm_s,
                            rows.chunk_seg, rows.seg_rows, fresh,
                            cfg.mamba_chunk_size)
                        ys.append(y)
                if n_dec:
                    live = valid[c:]
                    first = (positions[c:] == 0)
                    with jax.named_scope("conv"):
                        old = conv_s[:n_dec]
                        conv, tail = ssd.causal_conv_step(
                            xbc[c:], mixer.conv_weight, mixer.conv_bias,
                            jnp.where(first[:, None, None], 0, old))
                        conv_s = conv_s.at[:n_dec].set(
                            jnp.where(live[:, None, None], tail, old))
                    with jax.named_scope("ssm"):
                        xs, b, cc = mixer.scan_inputs(conv, u.dtype)
                        if cache.state_impl == "pallas":
                            # the layer's whole array, in place: live
                            # rows' tiles alone cross HBM
                            y, ssm_s = ssd.ssd_step_kernel(
                                xs, dt[c:], mixer.A, b, cc, mixer.D,
                                ssm_s, live, first)
                        else:
                            old = ssm_s[:n_dec]
                            y, new = ssd.ssd_step(
                                xs, dt[c:], mixer.A, b, cc, mixer.D,
                                jnp.where(
                                    (first & live)[:, None, None, None],
                                    0.0, old))
                            ssm_s = ssm_s.at[:n_dec].set(new)
                        ys.append(y)
                with jax.named_scope("ssm"):
                    out = mixer.finish(
                        ys[0] if len(ys) == 1 else jnp.concatenate(ys), z)
                conv_state[i_st], ssm_state[i_st] = conv_s, ssm_s
                i_st += 1
            x = x + r * out
            x, rows_held = layer.experts(x, r, valid, cache.moe_impl)
            aux.append(rows_held)
        pairs = jnp.sum(valid).astype(jnp.int32) * cfg.num_experts_per_tok
        aux = jnp.concatenate(
            [jnp.stack(aux), jnp.full((len(aux), 1), pairs, jnp.int32)], 1)
        cache = cache._replace(
            k_pages=k_pages, v_pages=v_pages,
            conv_state=tuple(conv_state), ssm_state=tuple(ssm_state))
        return x, cache, aux
