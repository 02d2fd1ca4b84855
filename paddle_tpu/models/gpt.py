"""GPT: decoder-only transformer LM (flagship model, BASELINE config 4).

The reference ships GPT via its ecosystem (fleetx/PaddleNLP) built on the
incubate fused transformer layers
(reference: python/paddle/incubate/nn/layer/fused_transformer.py:176
FusedMultiHeadAttention, :437 FusedFeedForward, :641
FusedTransformerEncoderLayer; CUDA kernels
paddle/fluid/operators/fused/fused_multi_transformer_op.cu) and the
Megatron tensor-parallel layers (VocabParallelEmbedding /
ColumnParallelLinear / RowParallelLinear,
python/paddle/distributed/fleet/meta_parallel/parallel_layers/mp_layers.py:30).

TPU-native design: one model definition carries logical sharding axes on
its weights ("vocab", "embed", "heads", "mlp"); the same code runs dense
on one chip or TP/FSDP/DP-sharded under a mesh — XLA inserts the
identity/allreduce pairs the reference hand-codes in mp_layers.py.
Attention dispatches to the Pallas flash kernel (paddle_tpu.ops).
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp

from .. import nn
from ..core import rng
from ..nn import functional as F
from ..nn import initializer as I
from ..nn.layer import Layer, LayerList


@dataclass
class GPTConfig:
    vocab_size: int = 50304
    hidden_size: int = 768
    num_layers: int = 12
    num_heads: int = 12
    num_kv_heads: Optional[int] = None   # grouped-query; None = num_heads
    ffn_hidden_size: Optional[int] = None  # None = 4*hidden
    max_position_embeddings: int = 1024
    hidden_dropout: float = 0.1
    attention_dropout: float = 0.1
    activation: str = "gelu"   # "swiglu" selects the gated MLP
    norm_type: str = "layer"   # "rms" selects RMSNorm (LLaMA-style)
    use_rope: bool = False     # rotary positions instead of learned
    rope_base: float = 10000.0
    initializer_range: float = 0.02
    layer_norm_epsilon: float = 1e-5
    tie_word_embeddings: bool = True
    use_flash: bool = True
    remat: bool = False  # rematerialize each block (jax.checkpoint)
    # context parallelism: attention runs as ring attention over the
    # mesh's sp axis (ops/ring_attention — K/V chunks rotate the ICI
    # ring; exact numerics). Composes with dp/fsdp/tp (partial-manual
    # over sp only); NOT with the pp trunk (nested manual axes) or the
    # decode cache. ring_chunk_size additionally streams each block's
    # K/V in tiles (flash-in-block) for true long-context footprints.
    sequence_parallel: bool = False
    ring_chunk_size: Optional[int] = None
    # lax.scan over the (identical-structure) decoder blocks instead of
    # a Python loop: the block lowers ONCE (compile time ~O(1) in depth
    # — the lever that makes 24-48-layer configs compile fast), and
    # with remat=True the recompute is structural (scan carries are the
    # only saved activations; XLA cannot CSE recomputation across scan
    # iterations, so the memory win survives every backend's pipeline).
    # Per-layer params are stacked to [L, ...] leaves at trace time —
    # one extra params-sized HBM copy per step, paid for depth>=12 by
    # the compile/memory wins. Decode caches fall back to the loop.
    scan_layers: bool = False
    # fused vocab path: forward returns (hidden, tied weight) and
    # GPTFusedPretrainingCriterion streams the loss over vocab chunks —
    # the [b, s, vocab] logits never exist in the train graph (PERF.md)
    fused_loss: bool = False

    def __post_init__(self):
        if self.ffn_hidden_size is None:
            self.ffn_hidden_size = 4 * self.hidden_size
        if self.num_kv_heads is None:
            self.num_kv_heads = self.num_heads

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_heads


# named presets; "gpt3-1.3b" is BASELINE config 4's hybrid-parallel target
PRESETS = {
    "gpt2-small": dict(hidden_size=768, num_layers=12, num_heads=12,
                       max_position_embeddings=1024),
    "gpt2-medium": dict(hidden_size=1024, num_layers=24, num_heads=16,
                        max_position_embeddings=1024),
    "gpt2-large": dict(hidden_size=1280, num_layers=36, num_heads=20,
                       max_position_embeddings=1024),
    "gpt2-xl": dict(hidden_size=1600, num_layers=48, num_heads=25,
                    max_position_embeddings=1024),
    "gpt3-1.3b": dict(hidden_size=2048, num_layers=24, num_heads=16,
                      max_position_embeddings=2048),
    "gpt3-6.7b": dict(hidden_size=4096, num_layers=32, num_heads=32,
                      max_position_embeddings=2048),
    "gpt3-13b": dict(hidden_size=5120, num_layers=40, num_heads=40,
                     max_position_embeddings=2048),
}


def llama_config(hidden_size: int = 2048, num_layers: int = 22,
                 num_heads: int = 16, num_kv_heads: int = 4,
                 vocab_size: int = 32000,
                 max_position_embeddings: int = 2048,
                 **overrides) -> GPTConfig:
    """LLaMA-style decoder: RoPE + RMSNorm + SwiGLU + GQA + untied
    head — the modern-LLM configuration of the same GPT skeleton."""
    base = dict(vocab_size=vocab_size, hidden_size=hidden_size,
                num_layers=num_layers, num_heads=num_heads,
                num_kv_heads=num_kv_heads,
                ffn_hidden_size=int(hidden_size * 8 / 3) // 128 * 128,
                max_position_embeddings=max_position_embeddings,
                hidden_dropout=0.0, attention_dropout=0.0,
                activation="swiglu", norm_type="rms", use_rope=True,
                tie_word_embeddings=False)
    base.update(overrides)
    return GPTConfig(**base)


def gpt_config(name: str, **overrides) -> GPTConfig:
    cfg = dict(PRESETS[name])
    cfg.update(overrides)
    return GPTConfig(**cfg)


# Device scopes (``Layer._scope``, ``jax.named_scope``): ``ln``, ``attn``
# (both projections and the attention itself), ``mlp``, ``embed``,
# ``lm_head``. Set on the sublayers the serving engine's programs call one
# by one, so a trace of either path names its operations alike.
def _norm(cfg: GPTConfig):
    cls = nn.RMSNorm if cfg.norm_type == "rms" else nn.LayerNorm
    norm = cls(cfg.hidden_size, epsilon=cfg.layer_norm_epsilon)
    norm._scope = "ln"
    return norm


class GPTAttention(Layer):
    """Causal self-attention with fused QKV and optional KV cache.

    Unlike nn.MultiHeadAttention (API-parity layer), the QKV projection
    is a single matmul — one big MXU op instead of three — and supports
    grouped-query heads. Column-parallel in, row-parallel out."""

    def __init__(self, cfg: GPTConfig):
        super().__init__()
        self.cfg = cfg
        h, hd = cfg.hidden_size, cfg.head_dim
        self.num_heads = cfg.num_heads
        self.num_kv_heads = cfg.num_kv_heads
        qkv_out = h + 2 * cfg.num_kv_heads * hd
        init = I.Normal(0.0, cfg.initializer_range)
        self.qkv_proj = nn.Linear(h, qkv_out, weight_attr=init,
                                  axes=("embed", "heads"),
                                  bias_axes=("heads",))
        self.out_proj = nn.Linear(h, h, weight_attr=I.Normal(
            0.0, cfg.initializer_range / math.sqrt(2 * cfg.num_layers)),
            axes=("heads", "embed"), bias_axes=(None,))
        self.qkv_proj._scope = self.out_proj._scope = "attn"

    def _sp_mesh(self):
        """The installed mesh when it has a real sp axis, else None
        (sequence_parallel degrades to plain attention off-mesh, so
        the same config runs single-device tests unchanged)."""
        from ..parallel.mesh import get_mesh
        mesh = get_mesh(required=False)
        if mesh is not None and mesh.axis_size("sp") > 1:
            return mesh
        return None

    def forward(self, x, attn_mask=None, cache=None,
                position_ids=None):
        b, s, h = x.shape
        hd = self.cfg.head_dim
        # [b, s] KEY-padding masks (the sp contract) are accepted by
        # every branch: the dense paths expand them to the additive
        # [b, 1, 1, s] broadcast form, so an sp-trained padded-batch
        # config still evaluates on a single device unchanged. The
        # sentinel is FINITE (softmax over an all--inf row is NaN) and
        # rows whose whole causal window is padded are zeroed after
        # attention — exactly what the ring path's fully-masked
        # handling produces (ops/ring_attention.py), keeping
        # dense/sp numerics interchangeable even for left-padding.
        dense_mask = attn_mask
        row_has_key = None
        if attn_mask is not None and attn_mask.ndim == 2:
            kpm_bool = attn_mask if attn_mask.dtype == jnp.bool_ \
                else attn_mask > -1e29
            am = jnp.where(kpm_bool, 0.0, -1e30).astype(jnp.float32)
            dense_mask = am[:, None, None, :]
            # causal: query r has a valid key iff any kpm[:, :r+1]
            row_has_key = jnp.cumsum(kpm_bool, axis=1) > 0   # [b, s]
        qkv = self.qkv_proj(x)
        q, k, v = jnp.split(
            qkv, [h, h + self.num_kv_heads * hd], axis=-1)
        q = q.reshape(b, s, self.num_heads, hd)
        k = k.reshape(b, s, self.num_kv_heads, hd)
        v = v.reshape(b, s, self.num_kv_heads, hd)
        if self.cfg.use_rope:
            # rotate BEFORE the cache write so cached keys carry their
            # absolute positions (decode-offset contract,
            # ops/rotary.py); tables fold to trace-time constants
            from ..ops.rotary import apply_rotary_pos_emb, rope_tables
            cos, sin = rope_tables(hd, self.cfg.max_position_embeddings,
                                   self.cfg.rope_base)
            if position_ids is None:
                start = cache[2] if cache is not None else 0
                position_ids = jnp.broadcast_to(
                    start + jnp.arange(s)[None, :], (b, s))
            q, k = apply_rotary_pos_emb(q, k, cos, sin,
                                        position_ids=position_ids)
        if cache is not None:
            k_cache, v_cache, idx = cache
            k_cache = jax.lax.dynamic_update_slice_in_dim(
                jnp.asarray(k_cache), k, idx, axis=1)
            v_cache = jax.lax.dynamic_update_slice_in_dim(
                jnp.asarray(v_cache), v, idx, axis=1)
            cache = (k_cache, v_cache, idx + s)
            k, v = k_cache, v_cache
            # causal within the new window AND only written cache slots:
            # query t (absolute idx+t) may attend keys at positions <= idx+t
            kl = k.shape[1]
            key_pos = jnp.arange(kl)[None, None, None, :]
            qry_pos = (idx + jnp.arange(s))[None, None, :, None]
            causal_mask = jnp.where(key_pos <= qry_pos, 0.0, -jnp.inf)
            if dense_mask is not None:  # e.g. padded-prompt mask
                if dense_mask.dtype == jnp.bool_:
                    dense_mask = jnp.where(dense_mask, 0.0, -jnp.inf)
                causal_mask = causal_mask + dense_mask
            with jax.named_scope("attn"):
                out = F.scaled_dot_product_attention(
                    q, k, v, attn_mask=causal_mask,
                    dropout_p=self.cfg.attention_dropout,
                    training=self.training, use_flash=False)
        elif self.cfg.sequence_parallel and \
                (sp_mesh := self._sp_mesh()) is not None:
            from ..ops.ring_attention import ring_attention
            if attn_mask is not None and attn_mask.shape != (b, s):
                # a general [.., sq, sk] mask would have to be
                # materialized per ring block pair; the serving/training
                # case is padded batches, which is a KEY-padding mask —
                # sharded and rotated with K/V, never fully materialized
                raise NotImplementedError(
                    "sequence_parallel attention takes a KEY-padding "
                    f"attn_mask of shape [batch, seq] = {(b, s)} (bool "
                    "True=attend, or additive float); got "
                    f"{attn_mask.shape}")
            if self.num_kv_heads != self.num_heads:
                # ring blocks want matching head counts; expand GQA
                # groups (correctness path — the K/V tiles are small)
                rep = self.num_heads // self.num_kv_heads
                k = jnp.repeat(k, rep, axis=2)
                v = jnp.repeat(v, rep, axis=2)
            dp = self.cfg.attention_dropout if self.training else 0.0
            with jax.named_scope("attn"):
                out = ring_attention(
                    q, k, v, causal=True, mesh=sp_mesh,
                    chunk_size=self.cfg.ring_chunk_size,
                    key_padding_mask=attn_mask,
                    dropout_p=dp,
                    # same key on every sp rank; ring_attention folds in
                    # the block's global coordinates (pipeline tick-RNG
                    # trick)
                    dropout_key=rng.next_key("sp_attn") if dp else None)
        else:
            # always causal (decoder-only); an extra additive mask (e.g.
            # padding) composes with it rather than replacing it
            with jax.named_scope("attn"):
                out = F.scaled_dot_product_attention(
                    q, k, v, attn_mask=dense_mask, is_causal=True,
                    dropout_p=self.cfg.attention_dropout,
                    training=self.training, use_flash=self.cfg.use_flash)
            if row_has_key is not None:
                out = jnp.where(row_has_key[:, :, None, None], out, 0.0)
        out = self.out_proj(out.reshape(b, s, h))
        if cache is not None:
            return out, cache
        return out


class GPTMLP(Layer):
    def __init__(self, cfg: GPTConfig):
        super().__init__()
        init = I.Normal(0.0, cfg.initializer_range)
        init_out = I.Normal(
            0.0, cfg.initializer_range / math.sqrt(2 * cfg.num_layers))
        self._swiglu = cfg.activation == "swiglu"
        in_width = 2 * cfg.ffn_hidden_size if self._swiglu \
            else cfg.ffn_hidden_size
        self.fc_in = nn.Linear(cfg.hidden_size, in_width,
                               weight_attr=init,
                               axes=("embed", "mlp"), bias_axes=("mlp",))
        self.fc_out = nn.Linear(cfg.ffn_hidden_size, cfg.hidden_size,
                                weight_attr=init_out,
                                axes=("mlp", "embed"), bias_axes=(None,))
        self.act = F.swiglu if self._swiglu else getattr(F, cfg.activation)
        self.dropout = nn.Dropout(cfg.hidden_dropout)
        self._scope = "mlp"

    def forward(self, x):
        return self.dropout(self.fc_out(self.act(self.fc_in(x))))


class GPTDecoderLayer(Layer):
    """Pre-LN decoder block (GPT-2/3 style)."""

    def __init__(self, cfg: GPTConfig):
        super().__init__()
        self.ln_1 = _norm(cfg)
        self.attn = GPTAttention(cfg)
        self.ln_2 = _norm(cfg)
        self.mlp = GPTMLP(cfg)
        self.dropout = nn.Dropout(cfg.hidden_dropout)

    def forward(self, x, attn_mask=None, cache=None,
                position_ids=None):
        a = self.attn(self.ln_1(x), attn_mask=attn_mask, cache=cache,
                      position_ids=position_ids)
        if cache is not None:
            a, cache = a
        x = x + self.dropout(a)
        x = x + self.mlp(self.ln_2(x))
        if cache is not None:
            return x, cache
        return x


class GPTEmbeddings(Layer):
    def __init__(self, cfg: GPTConfig):
        super().__init__()
        init = I.Normal(0.0, cfg.initializer_range)
        # vocab-parallel embedding (ref: mp_layers.py:30
        # VocabParallelEmbedding): shard the vocab dim over tp
        self.word_embeddings = nn.Embedding(
            cfg.vocab_size, cfg.hidden_size, weight_attr=init,
            axes=("vocab", "embed"))
        if not cfg.use_rope:  # rotary encodes positions in attention
            self.position_embeddings = nn.Embedding(
                cfg.max_position_embeddings, cfg.hidden_size,
                weight_attr=init, axes=(None, "embed"))
        self.dropout = nn.Dropout(cfg.hidden_dropout)
        self._use_rope = cfg.use_rope
        self._max_pos = cfg.max_position_embeddings
        self._scope = "embed"

    def forward(self, input_ids, position_ids=None):
        s = input_ids.shape[1]
        max_pos = self._max_pos
        if s > max_pos:
            raise ValueError(
                f"sequence length {s} exceeds max_position_embeddings "
                f"{max_pos} (an out-of-range gather would silently clamp)")
        from ..parallel.sharding import with_logical_constraint
        tok = with_logical_constraint(
            self.word_embeddings(input_ids), ("batch", "seq", None))
        if self._use_rope:
            return self.dropout(tok)
        if position_ids is None:
            position_ids = jnp.arange(s)[None, :]
        pos = with_logical_constraint(
            self.position_embeddings(position_ids), (None, "seq", None))
        return self.dropout(tok + pos)


class GPTModel(Layer):
    """Transformer trunk: embeddings → N decoder blocks → final LN."""

    def __init__(self, cfg: GPTConfig):
        super().__init__()
        self.cfg = cfg
        self.embeddings = GPTEmbeddings(cfg)
        self.layers = LayerList(
            [GPTDecoderLayer(cfg) for _ in range(cfg.num_layers)])
        self.ln_f = _norm(cfg)

    def forward(self, input_ids, position_ids=None, attn_mask=None,
                caches=None):
        from ..parallel.sharding import with_logical_constraint
        x = self.embeddings(input_ids, position_ids)
        # activation layout anchor: batch over the data axes, hidden
        # replicated — fsdp-sharded params are all-gathered at use
        # (ZeRO-3), rather than letting fsdp leak into activation hidden
        # dims (which forced full-remat reshards in the partitioner)
        x = with_logical_constraint(x, ("batch", "seq", None))
        rope_pos = position_ids if self.cfg.use_rope else None
        new_caches = [] if caches is not None else None
        if self.cfg.scan_layers and caches is None:
            x = self._scan_trunk(x, attn_mask, rope_pos)
        else:
            for i, layer in enumerate(self.layers):
                if caches is not None:
                    x, c = layer(x, attn_mask=attn_mask, cache=caches[i],
                                 position_ids=rope_pos)
                    new_caches.append(c)
                elif self.cfg.remat:
                    # trade FLOPs for HBM: recompute the block in backward
                    x = jax.checkpoint(
                        lambda x, l=layer: l(x, attn_mask=attn_mask,
                                             position_ids=rope_pos))(x)
                else:
                    x = layer(x, attn_mask=attn_mask,
                              position_ids=rope_pos)
                x = with_logical_constraint(x, ("batch", "seq", None))
        x = self.ln_f(x)
        if caches is not None:
            return x, new_caches
        return x

    def _scan_trunk(self, x, attn_mask, rope_pos):
        """lax.scan over the decoder stack (cfg.scan_layers) — see
        nn.utils.scan_layer_stack for the mechanics (single-lowering
        depth loop, stacked [L, ...] params, per-layer dropout keys,
        structural remat). ref: the reference's depth loop is
        run-to-completion eager (incubate/nn/functional teaches fused
        blocks instead); scan-over-depth is the XLA-native form."""
        from ..nn.utils import scan_layer_stack
        from ..parallel.sharding import with_logical_constraint

        return scan_layer_stack(
            self.layers, x, remat=self.cfg.remat,
            constraint=lambda o: with_logical_constraint(
                o, ("batch", "seq", None)),
            rng_tag="scan_trunk", attn_mask=attn_mask,
            position_ids=rope_pos)


def _lm_logits(cfg: GPTConfig, embeddings: GPTEmbeddings, hidden,
               lm_head=None):
    """Shared head: tied-embedding matmul (bf16 under AMP; the loss
    upcasts to f32 for its log-softmax) or a separate lm_head."""
    with jax.named_scope("lm_head"):
        if cfg.tie_word_embeddings:
            from .. import amp
            w = embeddings.word_embeddings.weight  # [V, H]
            hidden, w = amp.white_cast(hidden, w)
            return jnp.einsum("bsh,vh->bsv", hidden, w)
        return lm_head(hidden)


class GPTForCausalLM(Layer):
    """GPT with a (tied) LM head and generation utilities."""

    def __init__(self, cfg: GPTConfig):
        super().__init__()
        self.cfg = cfg
        self.gpt = GPTModel(cfg)
        if not cfg.tie_word_embeddings:
            self.lm_head = nn.Linear(cfg.hidden_size, cfg.vocab_size,
                                     bias_attr=False,
                                     axes=("embed", "vocab"))

    def _logits(self, hidden):
        return _lm_logits(self.cfg, self.gpt.embeddings, hidden,
                          getattr(self, "lm_head", None))

    def forward(self, input_ids, position_ids=None, attn_mask=None,
                caches=None):
        out = self.gpt(input_ids, position_ids, attn_mask, caches)
        if caches is not None:
            hidden, new_caches = out
            return self._logits(hidden), new_caches
        if self.cfg.fused_loss and self.training:
            # hand (hidden, W [vocab, hidden]) to the fused criterion;
            # W rides the output so its gradient flows through
            # value_and_grad. NOTE: metrics attached to Model.prepare
            # see the hidden states during fused training — compute
            # accuracy-style metrics in eval (logits path) instead.
            if not self.cfg.tie_word_embeddings:
                return out, self.lm_head.weight.T  # Linear stores [H,V]
            return out, self.gpt.embeddings.word_embeddings.weight
        return self._logits(out)

    # -- the serving engine's forward over ragged rows ------------------
    def kv_cache_spec(self):
        """``(layers, kv_heads, head_dim)`` of the paged K/V pool."""
        cfg = self.cfg
        return cfg.num_layers, cfg.num_kv_heads, cfg.head_dim

    def state_cache_spec(self):
        """No recurrent state: K/V pages are the whole context."""
        return None

    def moe_aux_spec(self):
        """No routed experts: ``ragged_forward`` returns no counts."""
        return None

    def loop_aux_spec(self):
        """The stack runs once: ``ragged_forward`` returns no exit
        steps."""
        return None

    def ragged_logits(self, hidden):
        """``hidden`` [R, H] (after the final norm) -> logits [R, V]."""
        return self._logits(hidden[:, None])[:, 0]

    def ragged_forward(self, rows, cache):
        """The one walk every ``LLMEngine`` program makes (the model owns
        the forward, the engine the cache view). ``rows``: ``tokens``,
        ``positions``, ``limits`` [T] and ``tables`` [T, pages]: T token
        rows drawn from any mix of sequences (a decode batch, a prompt's
        chunk, both at once), each with its own block table and causal
        limit (0 = a padded or inactive row, whose K/V lands on scratch
        page 0). ``cache``: ``k_pages``, ``v_pages`` (the stacked pool),
        ``attention_impl``. Each row's K/V is written into its page, then
        the row attends its sequence's pages
        (:func:`~paddle_tpu.ops.paged_attention.ragged_paged_attention`):
        causal inside a chunk because a row's limit is its own position
        + 1 and earlier rows' K/V are already in the pool. Returns
        ``(hidden [T, H] after the final norm, cache, None)``."""
        from ..ops.paged_attention import (kv_page_size, kv_write,
                                           ragged_paged_attention)
        cfg, gpt = self.cfg, self.gpt
        hd = cfg.head_dim
        t = rows.tokens.shape[0]
        k_pages, v_pages = cache.k_pages, cache.v_pages
        ps = kv_page_size(k_pages)
        tables = jnp.clip(rows.tables, 0)
        pos_ids = rows.positions[None, :]                  # [1, T]
        x = gpt.embeddings(rows.tokens[None, :], position_ids=pos_ids)
        page_idx = jnp.take_along_axis(
            tables, (rows.positions // ps)[:, None], axis=1)[:, 0]
        page_idx = jnp.where(rows.limits > 0, page_idx, 0)
        offs = rows.positions % ps
        if cfg.use_rope:
            from ..ops.rotary import apply_rotary_pos_emb, rope_tables
            cos, sin = rope_tables(hd, cfg.max_position_embeddings,
                                   cfg.rope_base)
        for i, layer in enumerate(gpt.layers):
            h = layer.ln_1(x)
            qkv = layer.attn.qkv_proj(h)
            q, k, v = jnp.split(
                qkv, [cfg.hidden_size,
                      cfg.hidden_size + cfg.num_kv_heads * hd], axis=-1)
            q = q.reshape(1, t, cfg.num_heads, hd)
            k = k.reshape(1, t, cfg.num_kv_heads, hd)
            v = v.reshape(1, t, cfg.num_kv_heads, hd)
            if cfg.use_rope:
                q, k = apply_rotary_pos_emb(q, k, cos, sin,
                                            position_ids=pos_ids)
            k_pages = kv_write(k_pages, i, page_idx, offs, k[0])
            v_pages = kv_write(v_pages, i, page_idx, offs, v[0])
            att = ragged_paged_attention(q[0], k_pages, v_pages, tables,
                                         rows.limits,
                                         impl=cache.attention_impl,
                                         layer=i, n_chunk=rows.n_chunk)
            x = x + layer.attn.out_proj(
                att.reshape(1, t, cfg.hidden_size))
            x = x + layer.mlp(layer.ln_2(x))
        x = gpt.ln_f(x)
        return x[0], cache._replace(k_pages=k_pages, v_pages=v_pages), \
            None

    # -- decode-time KV cache -------------------------------------------
    def init_caches(self, batch_size: int, max_len: int, dtype=jnp.float32):
        cfg = self.cfg
        shape = (batch_size, max_len, cfg.num_kv_heads, cfg.head_dim)
        return [(jnp.zeros(shape, dtype), jnp.zeros(shape, dtype), 0)
                for _ in range(cfg.num_layers)]

    def generate(self, input_ids, max_new_tokens: int = 20,
                 temperature: float = 0.0, top_k: int = 0, seed: int = 0):
        """Greedy (temperature=0) or top-k sampled decoding with a KV
        cache. Eager loop — the serving path AOT-compiles a scan instead."""
        self.eval()
        b, s = input_ids.shape
        max_len = s + max_new_tokens
        if max_len > self.cfg.max_position_embeddings:
            raise ValueError(
                f"prompt {s} + max_new_tokens {max_new_tokens} exceeds "
                f"max_position_embeddings "
                f"{self.cfg.max_position_embeddings}")
        # the cache holds what the blocks compute: the weights' dtype
        # (a bf16-cast net writes bf16 K/V)
        caches = self.init_caches(
            b, max_len, dtype=next(p for _, p in
                                   self.named_parameters()).dtype)
        key = jax.random.PRNGKey(seed)
        # prefill
        logits, caches = self(input_ids, caches=caches)
        tokens = input_ids
        next_logits = logits[:, -1]
        for step in range(max_new_tokens):
            if temperature > 0.0:
                key, sub = jax.random.split(key)
                lg = next_logits / temperature
                if top_k > 0:
                    kth = jnp.sort(lg, axis=-1)[:, -top_k][:, None]
                    lg = jnp.where(lg < kth, -jnp.inf, lg)
                nxt = jax.random.categorical(sub, lg, axis=-1)
            else:
                nxt = jnp.argmax(next_logits, axis=-1)
            nxt = nxt[:, None]
            tokens = jnp.concatenate([tokens, nxt], axis=1)
            if step == max_new_tokens - 1:
                break
            pos = jnp.full((b, 1), s + step)
            next_logits, caches = self(nxt, position_ids=pos, caches=caches)
            next_logits = next_logits[:, -1]
        return tokens


class GPTForCausalLMPipe(Layer):
    """GPT composed with SPMD pipeline parallelism over the decoder trunk.

    The reference builds this as ``GPTForPretrainingPipe`` — a
    PipelineLayer of embedding/decoder/head segments dispatched by the
    1F1B runtime (fleet meta_parallel pp_layers.py:162,
    pipeline_parallel.py:82). TPU-native composition: embeddings, final
    LN and the (tied) LM head stay OUTSIDE the pipelined trunk —
    pp-replicated, their grads all-reduced by XLA at the shard boundary,
    replacing the reference's shared-embedding allreduce
    (pp_layers.py SharedLayerDesc) — while the structurally identical
    decoder blocks run under ``parallel.PipelineParallel`` with the
    circular schedule. The pipeline's output arrives sharded over pp on
    the batch dim, so the head/loss run data-parallel over pp for free.
    """

    def __init__(self, cfg: GPTConfig, num_microbatches: int = 1,
                 virtual_pp_degree: int = 1, mesh=None):
        super().__init__()
        from ..parallel import get_mesh
        from ..parallel.pipeline import PipelineLayer, PipelineParallel
        self.cfg = cfg
        if cfg.scan_layers:
            import warnings
            warnings.warn(
                "GPTForCausalLMPipe ignores cfg.scan_layers: the "
                "pipeline's tick scan + checkpointed tick body already "
                "provide the structural depth loop and remat")
        if cfg.sequence_parallel:
            raise ValueError(
                "sequence_parallel cannot compose with the pipelined "
                "trunk: ring attention's shard_map would nest inside "
                "the pipeline's manual pp region. Use sp with the "
                "dense GPTForCausalLM, or pp without sp")
        mesh = mesh or get_mesh(required=False)
        pp = mesh.axis_size("pp") if mesh is not None else 1
        num_stages = pp * virtual_pp_degree
        if cfg.num_layers % num_stages:
            raise ValueError(
                f"num_layers {cfg.num_layers} not divisible by "
                f"pp*virtual_pp_degree = {num_stages}")
        self.embeddings = GPTEmbeddings(cfg)
        blocks = [GPTDecoderLayer(cfg) for _ in range(cfg.num_layers)]
        mb_spec = mesh.batch_spec() if mesh is not None else None
        from jax.sharding import PartitionSpec as P
        self.pipe = PipelineParallel(
            PipelineLayer(blocks, num_stages=num_stages),
            num_microbatches=num_microbatches,
            virtual_pp_degree=virtual_pp_degree,
            mesh=mesh, mb_spec=mb_spec if mb_spec is not None else P(),
            remat=True)
        self.ln_f = _norm(cfg)
        if not cfg.tie_word_embeddings:
            self.lm_head = nn.Linear(cfg.hidden_size, cfg.vocab_size,
                                     bias_attr=False,
                                     axes=("embed", "vocab"))

    def _logits(self, hidden):
        return _lm_logits(self.cfg, self.embeddings, hidden,
                          getattr(self, "lm_head", None))

    def forward(self, input_ids, position_ids=None):
        x = self.embeddings(input_ids, position_ids)
        x = self.pipe(x)
        x = self.ln_f(x)
        if self.cfg.fused_loss and self.training:
            # compose pp with the streaming vocab path: the pipeline's
            # output arrives batch-sharded over pp, and the fused loss
            # keeps logits out of HBM on top of it
            if not self.cfg.tie_word_embeddings:
                return x, self.lm_head.weight.T
            return x, self.embeddings.word_embeddings.weight
        return self._logits(x)


class GPTGreedyDecoder(Layer):
    """AOT-servable generation: the whole greedy decode loop — prefill,
    KV cache, ``lax.scan`` over new tokens — compiles into ONE program,
    exportable with ``jit.save`` and served by the native predictor.

    The reference serves generation by re-entering AnalysisPredictor
    once per token from host code (inference/api/analysis_predictor.h),
    paying a host round-trip each step; here the loop lives on-device
    and the artifact's signature is prompt ids → generated ids."""

    def __init__(self, model: GPTForCausalLM, max_new_tokens: int):
        super().__init__()
        if max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1 (the prefill "
                             "always argmaxes one token)")
        self.model = model
        self.max_new_tokens = max_new_tokens

    def forward(self, input_ids):
        self.eval()  # decoding is inference (mirrors generate())
        cfg = self.model.cfg
        b, s = input_ids.shape
        max_len = s + self.max_new_tokens
        # symbolic s (shape-polymorphic export) defers this to runtime
        if isinstance(s, int) and max_len > cfg.max_position_embeddings:
            raise ValueError(
                f"prompt {s} + {self.max_new_tokens} new tokens exceeds "
                f"max_position_embeddings {cfg.max_position_embeddings}")
        caches = self.model.init_caches(b, max_len)
        logits, caches = self.model(input_ids, caches=caches)
        first = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)

        def step(carry, i):
            tok, caches = carry
            pos = jnp.full((b, 1), s, jnp.int32) + i
            lg, caches = self.model(tok[:, None], position_ids=pos,
                                    caches=caches)
            nxt = jnp.argmax(lg[:, -1], axis=-1).astype(jnp.int32)
            return (nxt, caches), tok

        (last, _), toks = jax.lax.scan(
            step, (first, caches), jnp.arange(self.max_new_tokens - 1))
        new = jnp.concatenate(
            [jnp.moveaxis(toks, 0, 1), last[:, None]], axis=1)
        return jnp.concatenate([input_ids.astype(jnp.int32), new],
                               axis=1)


class GPTPretrainingCriterion(Layer):
    """Shifted next-token cross entropy; the TP analog of the reference's
    ParallelCrossEntropy (mp_layers.py:251 / c_softmax_with_cross_entropy)
    falls out of sharding the vocab dim of logits."""

    def __init__(self, ignore_index: int = -100):
        super().__init__()
        self.ignore_index = ignore_index

    def forward(self, logits, labels):
        # logits [b, s, v], labels [b, s]: predict token t+1 at position t
        lg = logits[:, :-1].reshape(-1, logits.shape[-1])
        lb = labels[:, 1:].reshape(-1)
        return F.cross_entropy(lg, lb, ignore_index=self.ignore_index)


class GPTFusedPretrainingCriterion(Layer):
    """Streaming vocab-path loss for cfg.fused_loss=True models: takes
    (hidden [b, s, h], weight [v, h]) from the model's forward and
    computes shifted next-token cross entropy over vocab chunks —
    no [b, s, v] logits in HBM (ops/fused_xent.py)."""

    def __init__(self, ignore_index: int = -100):
        super().__init__()
        self.ignore_index = ignore_index

    def forward(self, *args):
        if len(args) == 2:
            # eval mode: the model emits dense logits — fall back to
            # the standard shifted cross entropy so evaluate()/fit with
            # eval_data works on fused_loss models
            logits, labels = args
            lg = logits[:, :-1].reshape(-1, logits.shape[-1])
            lb = labels[:, 1:].reshape(-1)
            return F.cross_entropy(lg, lb,
                                   ignore_index=self.ignore_index)
        hidden, weight, labels = args
        from .. import amp
        from ..ops.fused_xent import fused_linear_cross_entropy
        hidden, weight = amp.white_cast(hidden, weight, op="matmul")
        h = hidden[:, :-1].reshape(-1, hidden.shape[-1])
        lb = labels[:, 1:].reshape(-1)
        return fused_linear_cross_entropy(
            h, weight, lb, self.ignore_index)
