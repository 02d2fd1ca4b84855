"""Paged attention: decode-time attention over a block-paged KV cache.

Reference context: the reference's serving attention keeps one dense
[b, max_len, h, d] cache per request (fused_multi_transformer_op.cu);
continuous batching then wastes HBM on the padding between each
request's true length and max_len. The paged formulation (vLLM;
"Ragged Paged Attention" for TPU, arXiv:2604.15464 in PAPERS.md) stores
KV in fixed-size PAGES shared across requests, with a per-request block
table mapping logical positions to pages — HBM waste bounded by one
page per sequence.

TPU-native design: pages are gathered per request with one take() (XLA
lowers to a dynamic-gather the TPU does well at page granularity —
contiguous [page_size, kv_heads, d] blocks), then attention runs as
dense SDPA with a context-length mask. Static shapes throughout
(pages_per_seq is the compiled maximum; short sequences mask). The
fancy kernel in the paper fuses the gather into the attention loop —
that is a later Pallas optimization; this implementation fixes the
MEMORY model, which is the serving win, and is numerically exact.
"""

from __future__ import annotations

import math
from typing import NamedTuple, Optional, Union

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import _LANES, _default_interpret
from .flash_attention import DEFAULT_MASK_VALUE as _MASK_VALUE


# ---------------------------------------------------------------------------
# int8-quantized KV pool
# ---------------------------------------------------------------------------

# engine knob values for LLMEngine(kv_dtype=...): the storage dtype of
# the paged KV pool. "int8" stores QUANTIZED pages with a per-token
# scale table beside the pool (see QuantizedKV) — ~2x page capacity at
# fixed HBM; the rest are plain-array pools.
KV_DTYPES = {
    "f32": jnp.float32, "float32": jnp.float32,
    "bf16": jnp.bfloat16, "bfloat16": jnp.bfloat16,
    "f16": jnp.float16, "float16": jnp.float16,
    "int8": jnp.int8,
}


class QuantizedKV(NamedTuple):
    """An int8-quantized paged KV store: ``pages`` holds the quantized
    values, ``scales`` the symmetric absmax scale of every page ROW
    (one f32 per written token per layer, stored beside the pool).

    Scale granularity is per token-row, not per page, by design: a
    page FILLS INCREMENTALLY (decode writes one token per tick), so a
    page-global scale would have to rescale already-written rows
    whenever a later token's amplitude exceeds the page max —
    per-row scales make quantize-on-write local and deterministic
    (the same KV values always quantize to the same bytes, which is
    what keeps prefix-cache sharing and nonce-pinned replay exact).
    Storage overhead is 4 bytes per token per layer per K/V against
    ``kv_heads*head_dim`` 1-byte values (~6% at the smallest test
    heads, less at real widths).

    Shapes (matching the plain pool with a leading scale-free tail):
    ``pages`` [..., num_pages, page_size, kv_heads, head_dim] int8,
    ``scales`` [..., num_pages, page_size] f32."""

    pages: jax.Array
    scales: jax.Array


KVStore = Union[jax.Array, QuantizedKV]


def kv_zeros(shape, dtype) -> KVStore:
    """Allocate a zeroed KV store. ``dtype`` is a jnp dtype or a
    KV_DTYPES key; int8 yields a :class:`QuantizedKV` (scale table
    beside the pool), anything else a plain array."""
    if isinstance(dtype, str):
        dtype = KV_DTYPES[dtype]
    if dtype == jnp.int8:
        return QuantizedKV(jnp.zeros(shape, jnp.int8),
                           jnp.zeros(shape[:-2], jnp.float32))
    return jnp.zeros(shape, dtype)


def kv_layer(store: KVStore, i) -> KVStore:
    """Per-layer view of a [L, ...]-stacked store (what the attention
    entry point consumes)."""
    with jax.named_scope("kv_layer"):
        if isinstance(store, QuantizedKV):
            return QuantizedKV(store.pages[i], store.scales[i])
        return store[i]


def kv_page_size(store: KVStore) -> int:
    return (store.pages if isinstance(store, QuantizedKV)
            else store).shape[-3]


def kv_nbytes(store: KVStore) -> int:
    """Device bytes of the store INCLUDING the scale table — the
    honest per-pool figure the memory ledger denominates pages in."""
    if isinstance(store, QuantizedKV):
        return store.pages.nbytes + store.scales.nbytes
    return store.nbytes


def kv_scale_nbytes(store: KVStore) -> int:
    """Bytes of the scale table alone (0 for plain stores) — the
    ledger's distinct ``scale_table`` row."""
    return store.scales.nbytes if isinstance(store, QuantizedKV) else 0


def quantize_kv(rows, eps: float = 1e-8):
    """Per-token symmetric absmax int8 quantization of KV rows
    [..., kv_heads, head_dim] -> (int8 rows, f32 scales [...]).
    Deterministic (pure function of the values): identical KV always
    produces identical quantized bytes, so cache-on/off and retried
    streams stay identical under quantization."""
    x = rows.astype(jnp.float32)
    amax = jnp.max(jnp.abs(x), axis=(-2, -1))
    scale = jnp.maximum(amax, eps) / 127.0
    q = jnp.clip(jnp.round(x / scale[..., None, None]),
                 -127, 127).astype(jnp.int8)
    return q, scale


def kv_write(store: KVStore, layer, page_idx, offs, rows) -> KVStore:
    """Scatter new KV rows into the pool at (layer, page_idx, offs),
    quantizing on write for :class:`QuantizedKV` stores (the scale
    lands beside the page row). ``rows`` [..., kv_heads, head_dim]
    with ``page_idx``/``offs`` broadcast over the leading dims —
    exactly the ``.at[i, page_idx, offs].set`` contract the engine's
    layers already use, made dtype-aware in ONE place."""
    with jax.named_scope("kv_write"):
        if isinstance(store, QuantizedKV):
            q, s = quantize_kv(rows)
            return QuantizedKV(
                store.pages.at[layer, page_idx, offs].set(q),
                store.scales.at[layer, page_idx, offs].set(s))
        return store.at[layer, page_idx, offs].set(
            rows.astype(store.dtype))


def _split_kv(store: KVStore):
    if isinstance(store, QuantizedKV):
        return store.pages, store.scales
    return store, None


class PagedKVCache:
    """Page-pool KV storage + per-request block tables.

    k/v pages: [num_pages, page_size, kv_heads, head_dim]; block table
    [max_seqs, pages_per_seq] of page ids (-1 = unallocated);
    context_lens [max_seqs]. Host-side allocation (serving control
    plane), device-side tensors."""

    def __init__(self, num_pages: int, page_size: int, kv_heads: int,
                 head_dim: int, max_seqs: int, pages_per_seq: int,
                 dtype=jnp.float32):
        self.page_size = page_size
        self.k_pages = jnp.zeros((num_pages, page_size, kv_heads,
                                  head_dim), dtype)
        self.v_pages = jnp.zeros_like(self.k_pages)
        self.block_tables = jnp.full((max_seqs, pages_per_seq), -1,
                                     jnp.int32)
        self.context_lens = jnp.zeros((max_seqs,), jnp.int32)
        self._free = list(range(num_pages - 1, -1, -1))

    def allocate(self, seq: int, n_tokens: int) -> None:
        """Reserve pages for n_tokens of sequence ``seq``."""
        need = -(-n_tokens // self.page_size)
        if need > self.block_tables.shape[1]:
            raise ValueError(
                f"sequence {seq} needs {need} pages but the block "
                f"table holds {self.block_tables.shape[1]} "
                f"(pages_per_seq); raise pages_per_seq or evict")
        have = int((self.block_tables[seq] >= 0).sum())
        for slot in range(have, need):
            if not self._free:
                raise RuntimeError("KV page pool exhausted")
            page = self._free.pop()
            self.block_tables = self.block_tables.at[seq, slot].set(page)

    def free(self, seq: int) -> None:
        for pid in [int(p) for p in self.block_tables[seq] if p >= 0]:
            self._free.append(pid)
        self.block_tables = self.block_tables.at[seq].set(-1)
        self.context_lens = self.context_lens.at[seq].set(0)

    def append(self, seq: int, k_new, v_new) -> None:
        """Write [t, kv_heads, d] new tokens at the sequence's end.
        Tokens are written one contiguous slice per TOUCHED PAGE (a
        per-token .at[].set would copy the whole pool per token)."""
        t = int(k_new.shape[0])
        start = int(self.context_lens[seq])
        self.allocate(seq, start + t)
        ps = self.page_size
        i = 0
        while i < t:
            pos = start + i
            page = int(self.block_tables[seq, pos // ps])
            off = pos % ps
            span = min(ps - off, t - i)
            self.k_pages = self.k_pages.at[page, off:off + span].set(
                k_new[i:i + span])
            self.v_pages = self.v_pages.at[page, off:off + span].set(
                v_new[i:i + span])
            i += span
        self.context_lens = self.context_lens.at[seq].set(start + t)


def paged_attention_kernel(q, k_pages, v_pages, block_tables,
                           context_lens, scale: Optional[float] = None,
                           interpret: Optional[bool] = None,
                           k_scales=None, v_scales=None):
    """Fused Pallas decode attention over paged KV (the "fancy kernel"
    the module docstring deferred; Ragged-Paged-Attention lineage).

    Same contract as :func:`paged_attention`. The difference is the
    memory traffic: the XLA path GATHERS every sequence's full padded
    context ([B, pages_per_seq*page_size, H, D]) into HBM before the
    dense attention reads it again; here the kernel's BlockSpec index
    map reads the SCALAR-PREFETCHED block table directly, so each grid
    step streams exactly one real page from the pool into VMEM —
    traffic scales with the true context length (``pl.when`` skips
    pages past it entirely), and nothing is materialized in between.

    Grid: (batch, pages_per_seq); the page dim is sequential so the
    online-softmax scratch (acc/m/l) carries across it. One grid step
    holds the page for ALL kv heads: the block is
    ``(1, page_size, kv_heads, d)``, whose trailing two dims are the
    pool's own, which is what the TPU lowering requires of a block
    that is not (8, 128)-divisible. With the page laid out
    [page_size, kv_heads, d] (heads on sublanes, d on lanes) a
    single query row per head is a broadcast-multiply and a lane
    reduction, so the step runs on the VPU with no relayout: decode
    attention is matrix-vector work and has nothing for the MXU. GQA
    is native: q arrives as [group, kv_heads, d] and each group row
    reuses the page in VMEM.

    int8 KV (``k_scales``/``v_scales`` [num_pages, page_size]):
    dequantization happens IN-KERNEL — each grid step brings the
    page's f32 scale row into SMEM alongside its int8 block and
    multiplies in VMEM, so HBM traffic stays at the quantized byte
    count (the whole point of the int8 pool).

    ``interpret`` defaults to the module switch
    ``flash_attention.INTERPRET`` (False: the kernel compiles for the
    TPU or raises).
    """
    if interpret is None:
        interpret = _default_interpret()
    b, n_heads, d = q.shape
    n_pages, page_size, kv_heads, _ = k_pages.shape
    pages_per_seq = block_tables.shape[1]
    group = n_heads // kv_heads
    sm_scale = scale if scale is not None else 1.0 / math.sqrt(d)
    quantized = k_scales is not None

    # [b, group, kv_heads, d]: a group row is one (kv_heads, d) tile
    qg = q.reshape(b, kv_heads, group, d).transpose(0, 2, 1, 3)
    tables = jnp.clip(block_tables, 0).astype(jnp.int32)
    lens = context_lens.astype(jnp.int32)

    def kernel(ctx_ref, tbl_ref, q_ref, k_ref, v_ref, *rest):
        if quantized:
            ks_ref, vs_ref, o_ref, acc_ref, m_ref, l_ref = rest
        else:
            o_ref, acc_ref, m_ref, l_ref = rest
        bi = pl.program_id(0)
        j = pl.program_id(1)

        @pl.when(j == 0)
        def _init():
            acc_ref[...] = jnp.zeros_like(acc_ref)
            m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
            l_ref[...] = jnp.zeros_like(l_ref)

        ctx = ctx_ref[bi]

        @pl.when(j * page_size < ctx)
        def _compute():
            k = k_ref[0].astype(jnp.float32)     # [page_size, kvh, d]
            v = v_ref[0].astype(jnp.float32)
            if quantized:
                # dequantize in VMEM: one SMEM scalar per page row
                k = jnp.stack([k[p] * ks_ref[0, 0, p]
                               for p in range(page_size)])
                v = jnp.stack([v[p] * vs_ref[0, 0, p]
                               for p in range(page_size)])
            row = jax.lax.broadcasted_iota(
                jnp.int32, (page_size, kv_heads, 1), 0)
            valid = row < ctx - j * page_size
            for g in range(group):
                qb = q_ref[0, g].astype(jnp.float32)  # [kvh, d]
                s = jnp.sum(qb[None] * k, axis=-1,
                            keepdims=True) * sm_scale
                s = jnp.where(valid, s, _MASK_VALUE)  # [ps, kvh, 1]
                m_prev = m_ref[g, :, :1]              # [kvh, 1]
                l_prev = l_ref[g, :, :1]
                m_new = jnp.maximum(m_prev, jnp.max(s, axis=0))
                alpha = jnp.exp(m_prev - m_new)
                p = jnp.exp(s - m_new[None])
                l_new = alpha * l_prev + jnp.sum(p, axis=0)
                acc_ref[g] = acc_ref[g] * alpha + jnp.sum(p * v,
                                                          axis=0)
                m_ref[g] = jnp.broadcast_to(m_new, m_ref.shape[1:])
                l_ref[g] = jnp.broadcast_to(l_new, l_ref.shape[1:])

        @pl.when(j == pages_per_seq - 1)
        def _finalize():
            l = l_ref[:, :, :1]
            l_safe = jnp.where(l == 0.0, 1.0, l)  # empty slot → zeros
            o_ref[0] = (acc_ref[...] / l_safe).astype(o_ref.dtype)

    # the paged gather: this index map IS the block table read
    page_spec = pl.BlockSpec((1, page_size, kv_heads, d),
                             lambda bi, j, ctx, tbl: (tbl[bi, j], 0,
                                                      0, 0))
    q_spec = pl.BlockSpec((1, group, kv_heads, d),
                          lambda bi, j, ctx, tbl: (bi, 0, 0, 0))
    in_specs = [q_spec, page_spec, page_spec]
    operands = [lens, tables, qg, k_pages, v_pages]
    if quantized:
        # the page's scale row rides beside its int8 block, in SMEM
        # (scalar reads); [num_pages, 1, page_size] so that the block's
        # trailing dims are the array's own
        scale_spec = pl.BlockSpec(
            (1, 1, page_size),
            lambda bi, j, ctx, tbl: (tbl[bi, j], 0, 0),
            memory_space=pltpu.SMEM)
        in_specs += [scale_spec, scale_spec]
        operands += [
            k_scales.astype(jnp.float32).reshape(n_pages, 1, page_size),
            v_scales.astype(jnp.float32).reshape(n_pages, 1, page_size)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, pages_per_seq),
        in_specs=in_specs,
        out_specs=q_spec,
        scratch_shapes=[
            pltpu.VMEM((group, kv_heads, d), jnp.float32),
            pltpu.VMEM((group, kv_heads, _LANES), jnp.float32),
            pltpu.VMEM((group, kv_heads, _LANES), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, group, kv_heads, d),
                                       q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="paged_attention",
    )(*operands)
    return out.transpose(0, 2, 1, 3).reshape(b, n_heads, d)


def ragged_paged_attention(q, kv_k: KVStore, kv_v: KVStore,
                           token_tables, token_lens,
                           scale: Optional[float] = None,
                           impl: str = "xla"):
    """THE ragged paged-attention entry point: ONE op serving every
    attention shape the engine dispatches — single-token decodes,
    chunked-prefill suffixes, speculative-verify windows, and a MIXED
    batch of all of them at once (the Ragged Paged Attention
    formulation, PAPERS.md #1) — over a plain OR int8-quantized
    (:class:`QuantizedKV`) paged pool.

    q: [T, heads, d] — T tokens drawn from ANY mix of sequences;
    token_tables: [T, pages_per_seq] — row t is the block table of
    token t's sequence (rows of the same sequence repeat it);
    token_lens: [T] — token t attends the first ``token_lens[t]``
    cached positions of its sequence (its own inclusive; 0 = padding
    or inactive slot -> zero output row). Returns [T, heads, d].
    GQA: heads may be a multiple of kv_heads.

    The T=batch single-token case IS the decode step
    (:func:`paged_attention` aliases here); the rectangular [B, K]
    case flattens to it (:func:`paged_attention_chunk`); causality
    inside a prefill chunk falls out of the per-token limit, because
    a later token of the same sequence has a strictly larger
    ``token_lens`` and earlier chunk tokens' K/V are already
    scattered into the pool. A mixed prefill+decode tick is just a
    batch whose rows happen to come from both phases — nothing in
    the contract distinguishes them, which is what lets the engine
    collapse its alternating tick loop into one dispatch.

    Pure-functional and trace-safe by contract: every input may be a
    traced value, so the op is callable from inside a ``lax.scan``
    body — the engine's fused slab carries the (possibly quantized)
    pool in its :class:`DecodeCarry` and calls this per tick.

    ``impl``: ``"xla"`` (gather + dense masked softmax, f32
    accumulate), ``"pallas"`` (fused kernel streaming one real page
    per grid step, int8 dequantized in VMEM), or ``"reference"``
    (:func:`ragged_paged_attention_reference` — full-f32 exactness
    baseline, kept callable for the int8 tolerance tests)."""
    kp, ks = _split_kv(kv_k)
    vp, vs = _split_kv(kv_v)
    if impl == "pallas":
        return paged_attention_kernel(q, kp, vp, token_tables,
                                      token_lens, scale=scale,
                                      k_scales=ks, v_scales=vs)
    if impl == "reference":
        return ragged_paged_attention_reference(
            q, kv_k, kv_v, token_tables, token_lens,
            scale=scale).astype(q.dtype)
    if impl != "xla":
        raise ValueError(f"unknown impl {impl!r}")
    d = q.shape[-1]
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    # the K=1 case of the gathered core, with limit = token_lens
    # DIRECTLY (a single cached token — limit 1 — still attends)
    out = _gathered_attention(q[:, None], kp, vp, token_tables,
                              token_lens[:, None], scale,
                              k_scales=ks, v_scales=vs)
    return out[:, 0]


def ragged_paged_attention_reference(q, kv_k: KVStore, kv_v: KVStore,
                                     token_tables, token_lens,
                                     scale: Optional[float] = None):
    """f32-accumulate reference path (the exactness baseline): same
    contract as :func:`ragged_paged_attention`, but q, the
    (dequantized) pages, and every intermediate are f32 end to end
    and the result is returned in f32. This is what the int8
    quantization TOLERANCE is measured against in tests and in
    ``llm_bench --kv-dtype``; it is deliberately simple rather than
    fast."""
    kp, ks = _split_kv(kv_k)
    vp, vs = _split_kv(kv_v)
    d = q.shape[-1]
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    out = _gathered_attention(q.astype(jnp.float32)[:, None],
                              kp, vp, token_tables,
                              token_lens[:, None], scale,
                              k_scales=ks, v_scales=vs)
    return out[:, 0]


def paged_attention_chunk(q, k_pages, v_pages, block_tables, base_lens,
                          scale: Optional[float] = None,
                          impl: str = "xla"):
    """Multi-query decode attention over paged KV (the speculative-
    verify / chunked-prefill step): ``q`` carries K NEW tokens per
    sequence whose K/V were just written at positions
    ``base_lens[b] .. base_lens[b]+K-1``; query j attends the first
    ``base_lens[b]+j+1`` cached positions (its own inclusive) —
    causal within the chunk, full context before it.

    q: [B, K, heads, d]; base_lens [B] = valid tokens BEFORE the chunk
    (0 = inactive slot → zero output rows). Returns [B, K, heads, d].

    DEPRECATED ALIAS: the rectangular [B, K] case of
    :func:`ragged_paged_attention` (rows flattened, each carrying its
    sequence's table and its own causal limit) — kept for source
    compatibility; new call sites should use the ragged entry point.
    """
    b, kq, h, d = q.shape
    limit = jnp.where(base_lens[:, None] > 0,
                      base_lens[:, None] + jnp.arange(kq)[None, :] + 1,
                      0)                                  # [B, K]
    out = ragged_paged_attention(
        q.reshape(b * kq, h, d), k_pages, v_pages,
        jnp.repeat(block_tables, kq, axis=0), limit.reshape(-1),
        scale=scale, impl=impl)
    return out.reshape(b, kq, h, d)


def paged_attention_ragged(q, k_pages, v_pages, token_tables,
                           token_lens, scale: Optional[float] = None,
                           impl: str = "xla"):
    """DEPRECATED ALIAS of :func:`ragged_paged_attention` (the entry
    point subsumed it verbatim — same contract, same shapes); kept
    for source compatibility with pre-consolidation call sites."""
    return ragged_paged_attention(q, k_pages, v_pages, token_tables,
                                  token_lens, scale=scale, impl=impl)


def paged_attention(q, k_pages, v_pages, block_tables, context_lens,
                    scale: Optional[float] = None, impl: str = "xla"):
    """Single-query attention over paged KV (the decode step).

    q: [B, heads, d]; k/v_pages: [num_pages, page_size, kv_heads, d]
    (or a :class:`QuantizedKV`); block_tables: [B, pages_per_seq]
    page ids (-1 pads); context_lens: [B] valid token counts.
    Returns [B, heads, d]. GQA: heads may be a multiple of kv_heads.

    DEPRECATED ALIAS: the T=batch single-token case of
    :func:`ragged_paged_attention` — the shapes are literally the
    ragged contract already (one table row and one limit per query
    token), so this delegates unchanged. Trace-safety contract
    unchanged: callable from inside a ``lax.scan`` body."""
    return ragged_paged_attention(q, k_pages, v_pages, block_tables,
                                  context_lens, scale=scale, impl=impl)


def _gathered_attention(q, k_pages, v_pages, block_tables, limit,
                        scale, k_scales=None, v_scales=None):
    """Shared decode-attention core: gather the block table's pages,
    dequantize (optional per-row scales), expand GQA, masked fp32
    softmax. q [B, K, H, d]; limit [B, K] = attendable cached
    positions per query (0 → zero output row)."""
    b, kq, n_heads, d = q.shape
    _, page_size, kv_heads, _ = k_pages.shape
    pages_per_seq = block_tables.shape[1]

    L = pages_per_seq * page_size
    with jax.named_scope("kv_gather"):
        tables = jnp.clip(block_tables, 0)             # [B, P]
        k = jnp.take(k_pages, tables, axis=0)          # [B, P, ps, KVH, d]
        v = jnp.take(v_pages, tables, axis=0)
        if k_scales is not None:
            # int8 pool: dequantize the gathered rows (scale per page
            # row)
            k = k.astype(jnp.float32) * \
                jnp.take(k_scales, tables, axis=0)[..., None, None]
            v = v.astype(jnp.float32) * \
                jnp.take(v_scales, tables, axis=0)[..., None, None]
        k = k.reshape(b, L, kv_heads, d)
        v = v.reshape(b, L, kv_heads, d)
        if n_heads != kv_heads:
            rep = n_heads // kv_heads
            k = jnp.repeat(k, rep, axis=2)
            v = jnp.repeat(v, rep, axis=2)

    with jax.named_scope("attn_scores"):
        logits = jnp.einsum("bqhd,blhd->bhql", q.astype(jnp.float32),
                            k.astype(jnp.float32)) * scale   # [B,H,K,L]
        mask = jnp.arange(L)[None, None, :] < limit[:, :, None]  # [B,K,L]
        logits = jnp.where(mask[:, None], logits, -jnp.inf)
        p = jax.nn.softmax(logits, axis=-1)
        # fully-masked rows (limit 0, e.g. a freed slot): zeros, not NaN
        p = jnp.where(limit[:, None, :, None] > 0, p, 0.0)
        out = jnp.einsum("bhql,blhd->bqhd", p, v.astype(jnp.float32))
        return out.astype(q.dtype)
