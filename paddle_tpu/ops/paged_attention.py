"""Paged attention: decode-time attention over a block-paged KV cache.

Reference context: the reference's serving attention keeps one dense
[b, max_len, h, d] cache per request (fused_multi_transformer_op.cu);
continuous batching then wastes HBM on the padding between each
request's true length and max_len. The paged formulation (vLLM;
"Ragged Paged Attention" for TPU, arXiv:2604.15464 in PAPERS.md) stores
KV in fixed-size PAGES shared across requests, with a per-request block
table mapping logical positions to pages — HBM waste bounded by one
page per sequence.

Two paths behind one entry point (:func:`ragged_paged_attention`).
On a TPU the engine takes :func:`paged_attention_kernel`: a Pallas
kernel that streams each row's LIVE pages straight out of the stacked
``[L, num_pages, ...]`` pool, a block of pages a step, with the online
softmax in float32 — the bytes a tick moves follow the live context. A
program's packed prompt rows (``n_chunk``) take the same walk a TILE of
rows at a time (:func:`_paged_attention_chunk_call`): a sequence's pages
are fetched once for up to 32 of its rows, not once a row.
Anywhere else it takes the gathered path
(:func:`_gathered_attention`): every entry of a row's block table
gathered with one take() at page granularity (contiguous
[page_size, kv_heads, d] blocks), then dense SDPA with a
context-length mask. Static shapes throughout (pages_per_seq is the
compiled maximum; short sequences mask). It is numerically exact, the
baseline the kernel is held to, and it moves ``max_len`` of K and V
for every row whatever the row holds.
"""

from __future__ import annotations

import functools
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
    layers already use, made dtype-aware in ONE place. A latent pool
    (``[L, num_pages, page_size, width]``, no head axis) takes ``rows``
    [..., width] through the same line, once: it has no V to write."""
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


# what the kernel plans to hold in VMEM for the pages in flight (K and V,
# two slots each); the compiler's own temporaries come on top, inside
# the default scoped limit of 16 MiB
_PAGE_BUFFER_BYTES = 4 * 1024 * 1024
# tokens folded into the softmax state at a time
_GROUP_TOKENS = 64
# query heads a K/V head up to which the fold stays on the VPU
_VPU_GROUP_ROWS = 4
# float32 scores of one fold on the MXU (every query head against every K/V
# head's rows of the fold's tokens): a fold is as long as this lets it be
_MXU_SCORE_BYTES = 1024 * 1024
# tokens of a latent pool folded at a time: one MXU product a group
_LATENT_GROUP_TOKENS = 256
# what the query tiles over a latent pool may hold in VMEM: a tile's query,
# result and sums are ``32 rows x heads`` rows of the latent width
_LATENT_TILE_VMEM_BYTES = 64 << 20


def _pages_per_block(page_size: int, kv_heads: Optional[int], d: int, dtype,
                     pages_per_seq: int, pools: int = 2,
                     dv: Optional[int] = None) -> int:
    """Pages the kernel moves a step: as many as the page buffers (one a
    pool the walk reads, ``pools``: K and V, or the one pool of a latent
    group; each double-buffered) hold in ``_PAGE_BUFFER_BYTES``, a page
    counted at its tiled size in VMEM as it is STORED (kv heads padded to
    the dtype's sublane packing, d to the lane width; ``kv_heads`` None: a
    page without a head axis, ``[page_size, d]``, its tokens on the
    sublanes). ``dv``: the V pool's last dimension where it is not the K
    pool's (a page of each is then counted at its own width). Follows the
    shapes: 16 pages of 16 tokens for a bf16 pool with 16 heads of 128."""
    itemsize = jnp.dtype(dtype).itemsize
    sublanes = 8 * max(1, 4 // itemsize)

    def page_bytes(width):
        lanes = -(-width // _LANES) * _LANES
        if kv_heads is None:
            return -(-page_size // sublanes) * sublanes * lanes * itemsize
        return (page_size * -(-kv_heads // sublanes) * sublanes
                * lanes * itemsize)

    widths = [d] * pools if dv is None else [d, dv]
    return max(1, min(pages_per_seq, _PAGE_BUFFER_BYTES
                      // (2 * sum(map(page_bytes, widths)))))


def _page_copier(meta_ref, tbl_ref, k_hbm, v_hbm, k_buf, v_buf, sems):
    """Both walks' page traffic: ``copies(row, first_page, n, slot, do)``
    does ``do`` (start or wait) to the K and the V copy of the ``n`` pages
    from column ``first_page`` of ``row``'s table, out of layer
    ``meta_ref[0]`` of the stacked pool into buffer slot ``slot``. A latent
    group has ONE pool (``v_hbm`` None): its page is copied once and read
    for the scores and for the values. ``k_buf`` a TUPLE of buffers: the
    key's lane tiles one buffer each (keys wider than 128 lanes under the
    query tiles, whose strided loads want a buffer one lane tile wide), a
    copy a lane tile a page, out of the same rows of the pool."""
    def copies(row, first_page, n, slot, do):
        def page(p, carry):
            src = (meta_ref[0], tbl_ref[row, first_page + p])
            if isinstance(k_buf, tuple):
                for c, column in enumerate(k_buf):
                    do(pltpu.make_async_copy(
                        k_hbm.at[src].at[:, :, pl.ds(c * _LANES, _LANES)],
                        column.at[slot, p], sems.at[0, slot]))
            else:
                do(pltpu.make_async_copy(k_hbm.at[src], k_buf.at[slot, p],
                                         sems.at[0, slot]))
            if v_hbm is not None:
                do(pltpu.make_async_copy(v_hbm.at[src], v_buf.at[slot, p],
                                         sems.at[1, slot]))
            return carry

        jax.lax.fori_loop(0, n, page, 0)

    return copies


def _refuse_unserved(latent: bool, quantized: bool, sinks, widths) -> None:
    """What no path serves, refused by name and never half-served: a sink
    or unequal K and V widths over an int8 pool (its scale a row is of ONE
    width, and its fold has no start state), a sink over a latent pool (no
    model asks for one)."""
    if sinks is not None and (quantized or latent):
        raise ValueError("sinks over " + ("an int8" if quantized else
                                          "a latent")
                         + " pool: no path serves a softmax sink there")
    if quantized and widths is not None and widths[0] != widths[1]:
        raise ValueError(f"an int8 pool with keys of {widths[0]} beside "
                         f"values of {widths[1]}: no path serves unequal "
                         f"widths quantized")


def paged_attention_kernel(q, k_pages, v_pages, block_tables,
                           context_lens, layer=None,
                           scale: Optional[float] = None,
                           interpret: Optional[bool] = None,
                           k_scales=None, v_scales=None, starts=None,
                           n_chunk: int = 0,
                           value_dim: Optional[int] = None, sinks=None):
    """Fused Pallas attention over the paged KV pool (Ragged-Paged-
    Attention lineage): every row of ``q`` attends the first
    ``context_lens[row]`` cached positions of the sequence whose block
    table is ``block_tables[row]``.

    The pool is read where it lives. ``k_pages`` / ``v_pages`` are the
    engine's STACKED stores ``[L, num_pages, page_size, kv_heads, d]``
    and ``layer`` (an int or a traced scalar) says which layer this call
    attends: the kernel finds a page at ``(layer, table[row, j])`` in
    HBM, so no layer is ever sliced out of the pool. A four-dimensional
    store (one layer's pages, ``layer`` None) is taken as its ``[1, ...]``
    view.

    The bytes moved follow the live context. The grid is the rows; a
    row walks only its own ``ceil(limit / page_size)`` live pages, a
    BLOCK of them a step (``_pages_per_block``: from the shapes and a
    VMEM budget), each page one async copy from HBM into one of two
    VMEM slots. While a block is computed the next one is in flight:
    the row's next block, or after its last the first block of the next
    row that has anything to attend, so a row's first pages are already
    on their way when its grid step begins. Rows with limit 0 cost a
    grid step and a zero row; pages past a row's limit are never
    fetched.

    A page sits in VMEM as it sits in the pool, ``[page_size, kv_heads,
    d]`` (heads on sublanes, d on lanes): a query row per head is a
    broadcast-multiply and a lane reduction on the VPU, with no
    relayout. Decode attention is matrix-vector work. Scores, the
    online softmax (acc / m / l scratch carried across a row's pages)
    and the weighted sum are float32; pages are read in the dtype they
    are stored in. A block's pages are folded into that state
    ``_GROUP_TOKENS`` tokens at a time (whole groups of pages, then the
    rest a page at a time): enough independent work a step to keep the
    VPU busy, few enough that the float32 copies stay small. GQA is
    native: q arrives as [group, kv_heads, d] and each group row reuses
    the pages in VMEM. Over ``_VPU_GROUP_ROWS`` query heads a K/V head
    that per-row work is the kernel's time (a page costs every group row
    a multiply and a lane reduction), and the fold becomes two MXU
    products over the pages as they lie (``attend_pages_on_mxu``): all
    query rows against all K/V heads' rows of the fold's tokens, read
    from the page buffer in the pool's type (no float32 copy of a page on
    this path), float32 for the scores, the sums and the softmax state.
    Such a fold is one chain of product, softmax and product, and what
    it costs is mostly the chain's latency: it takes as many tokens as
    its float32 scores may (``_MXU_SCORE_BYTES``; a whole block of 16
    pages at 8 K/V heads of 128), whole groups only, the pages of a
    block's last group that no copy wrote masked out of the second
    product. One fold for many heads: a K/V head at a time (eight chains
    of 6-row products a block where this is one) was measured beside it
    and is slower at both of the shapes that take this path (PR 41).

    int8 KV (``k_scales``/``v_scales`` ``[L, num_pages, page_size]``, or
    without the layer axis beside a four-dimensional store): the pages
    cross HBM as int8 and are dequantized in VMEM. The scale rows of a
    row's table are gathered beside the kernel (4 bytes a token where a
    page row has ``kv_heads * d``) and ride into SMEM with the row.

    ``starts`` [rows] (a sliding window's lower bound; None = 0, and
    the program of today): row ``r`` attends positions ``starts[r] <= j
    < context_lens[r]``. Its walk begins at page ``starts[r] //
    page_size``: no page before that one is fetched, and the tokens of
    that page below ``starts[r]`` are masked, so the bytes a windowed
    row moves follow its window and not its length.

    ``n_chunk`` (a Python int; 0 = none, and the program of today): the
    first ``n_chunk`` rows are PACKED PROMPT ROWS, the rows of one
    sequence contiguous and in order, and go through QUERY TILES
    (:func:`_paged_attention_chunk_call`): up to ``chunk_tile_rows`` of
    them against each page of their sequence ONCE, where the row walk
    fetches it once a row. Every row attends exactly the positions it
    attends in the row walk. The rows after them take the row walk above.
    An int8 pool keeps the row walk for all its rows (its scale rows ride
    in SMEM a row; no cell runs one).

    A LATENT pool (``v_pages`` None, ``value_dim`` given): ONE stacked
    store ``[L, num_pages, page_size, width]`` whose row is a token's key
    for every query head and whose first ``value_dim`` columns are its
    value (``page_pool.CacheGroup.value_dim``). ``q`` is ``[rows, heads,
    width]``, the result ``[rows, heads, value_dim]``. Both walks copy a
    page ONCE into one buffer and read it for both products, on the MXU
    (every query head against the rows as they lie: tokens on sublanes,
    no head axis to pad).

    K AND V OF DIFFERENT WIDTHS: ``v_pages`` ``[L, num_pages, page_size,
    kv_heads, dv]`` beside keys of ``d``: ``q`` [rows, heads, d] -> [rows,
    heads, dv]. Each pool has its own page buffer and the sums are ``dv``
    wide; nothing else differs (equal widths: the program of before).

    ``sinks`` [heads] float32 (None: none, and the program of today): query
    head ``h`` has a logit ``sinks[h]`` that joins its softmax's
    denominator and carries no value, ``o_h = sum_j e^{s_hj - m} v_j /
    (e^{sinks[h] - m} + sum_j e^{s_hj - m})``. It is the START STATE of the
    online softmax in both walks (``m = sinks[h], l = 1, acc = 0`` where
    ``-inf, 0, 0`` stand), so every fold after it is the fold of before. A
    row with nothing to attend (limit 0) is still a zero row.

    ``interpret`` defaults to the module switch
    ``flash_attention.INTERPRET`` (False: the kernel compiles for the
    TPU or raises).
    """
    if interpret is None:
        interpret = _default_interpret()
    latent = v_pages is None
    if latent and (value_dim is None or k_scales is not None):
        raise ValueError("a pool without V pages is a latent pool: it "
                         "needs value_dim and has no int8 form")
    _refuse_unserved(latent, k_scales is not None, sinks,
                     None if latent else (k_pages.shape[-1],
                                          v_pages.shape[-1]))
    if k_pages.ndim == (3 if latent else 4):
        k_pages = k_pages[None]
        v_pages = None if latent else v_pages[None]
        if k_scales is not None:
            k_scales, v_scales = k_scales[None], v_scales[None]
        layer = 0
    if latent:
        page_size, d = k_pages.shape[2:]
        block = _pages_per_block(page_size, None, d, k_pages.dtype,
                                 block_tables.shape[1], 1)
        # whole groups of _LATENT_GROUP_TOKENS are folded, never a rest
        fold = max(1, min(block, _LATENT_GROUP_TOKENS // page_size))
        block = block // fold * fold
    else:
        page_size, kv_heads, d = k_pages.shape[2:]
        block = _pages_per_block(page_size, kv_heads, d, k_pages.dtype,
                                 block_tables.shape[1], 2,
                                 v_pages.shape[-1])
        fold = max(1, min(block, _GROUP_TOKENS // page_size))
    layer = jnp.asarray(layer, jnp.int32)
    scale = scale if scale is not None else 1.0 / math.sqrt(d)

    def rows_of(part):
        return (q[part], block_tables[part], context_lens[part],
                None if starts is None else starts[part])

    def row_walk(q, block_tables, context_lens, starts):
        walk_block, walk_fold = block, fold
        if not latent and k_scales is None \
                and q.shape[1] // kv_heads > _VPU_GROUP_ROWS:
            # the fold on the MXU keeps no float32 copy of a page: as many
            # tokens as its scores allow, whole groups and never a rest
            walk_fold = max(1, min(block, _MXU_SCORE_BYTES // (
                4 * q.shape[1] * kv_heads * page_size)))
            walk_block = block // walk_fold * walk_fold
        return _paged_attention_call(
            q, k_pages, v_pages, block_tables, context_lens, layer,
            k_scales, v_scales, starts, sinks, scale=scale,
            interpret=bool(interpret), block=walk_block,
            group_pages=walk_fold, value_dim=value_dim)

    n_chunk = min(int(n_chunk), q.shape[0])
    if n_chunk <= 0 or k_scales is not None:
        return row_walk(q, block_tables, context_lens, starts)
    cq, ctables, clens, cstarts = rows_of(slice(0, n_chunk))
    tiled = _paged_attention_chunk_call(
        cq, k_pages, v_pages, ctables, clens, layer, cstarts, sinks,
        scale=scale, interpret=bool(interpret),
        block=fold if latent else block,
        qb=chunk_tile_rows(n_chunk), value_dim=value_dim)
    if n_chunk == q.shape[0]:
        return tiled
    return jnp.concatenate(
        [tiled, row_walk(*rows_of(slice(n_chunk, None)))])


@functools.partial(jax.jit, static_argnames=("scale", "interpret", "block",
                                             "group_pages", "value_dim"))
def _paged_attention_call(q, k_pages, v_pages, block_tables, context_lens,
                          layer, k_scales, v_scales, starts=None, sinks=None,
                          *, scale, interpret, block, group_pages,
                          value_dim=None):
    """:func:`paged_attention_kernel` on the stacked pool with a traced
    ``layer``. Jitted so that an engine program, which calls it once a
    layer with the same shapes, traces and lowers the kernel once."""
    quantized = k_scales is not None
    windowed = starts is not None
    sunk = sinks is not None
    latent = v_pages is None
    rows, n_heads, d = q.shape
    if latent:
        page_size, kv_heads, dv = k_pages.shape[2], 1, value_dim
    else:
        _, _, page_size, kv_heads, _ = k_pages.shape
        dv = v_pages.shape[-1]
    pages_per_seq = block_tables.shape[1]
    group = n_heads // kv_heads
    # few query heads a K/V head: a broadcast-multiply and a lane
    # reduction a group row on the VPU (decode attention is matrix-vector
    # work). Many (the per-page cost grows with every group row: 1.1 us a
    # page at 9, my chip run, PR 35): the fold as two MXU products
    on_mxu = group > _VPU_GROUP_ROWS and not quantized

    # [rows, group, kv_heads, d]: a group row is one (kv_heads, d) tile
    # (a latent pool: the heads' rows as they come, [rows, heads, d])
    qg = q if latent else \
        q.reshape(rows, kv_heads, group, d).transpose(0, 2, 1, 3)
    tables = jnp.clip(block_tables, 0).astype(jnp.int32)
    lens = jnp.clip(context_lens.astype(jnp.int32), 0,
                    pages_per_seq * page_size)
    # next_live[r]: the first row after r with anything to attend
    # (``rows`` when there is none); the prefetch across rows follows it
    row_ids = jnp.arange(rows, dtype=jnp.int32)
    live_from = jax.lax.cummin(
        jnp.where(lens > 0, row_ids, rows), reverse=True)
    next_live = jnp.concatenate(
        [live_from[1:], jnp.full((1,), rows, jnp.int32)])
    meta = jnp.stack([layer, live_from[0]])
    if windowed:
        starts = jnp.clip(starts.astype(jnp.int32), 0, lens)

    def kernel(len_ref, tbl_ref, next_ref, meta_ref, *rest):
        if windowed:
            start_ref, rest = rest[0], rest[1:]
        if latent:
            (q_ref, k_hbm, o_ref, k_buf, sems, slot_ref, acc_ref, m_ref,
             l_ref) = rest
            v_hbm = v_buf = None
        else:
            q_ref, k_hbm, v_hbm = rest[:3]
            rest = rest[3:]
            if quantized:
                ks_ref, vs_ref = rest[:2]
                rest = rest[2:]
            if sunk:
                sink_ref, rest = rest[0], rest[1:]
            (o_ref, k_buf, v_buf, sems, slot_ref, acc_ref, m_ref,
             l_ref) = rest
        r = pl.program_id(0)
        ctx = len_ref[r]

        def first_page_of(row):
            """The page a row's walk begins at: that of its lower
            bound."""
            return start_ref[row] // page_size if windowed else 0

        lo = start_ref[r] if windowed else 0
        page0 = first_page_of(r)
        n_pages = pl.cdiv(ctx, page_size) - page0
        n_blocks = pl.cdiv(n_pages, block)

        copies = _page_copier(meta_ref, tbl_ref, k_hbm, v_hbm, k_buf, v_buf,
                              sems)

        def block_copies(row, blk, slot, do):
            """``do`` (start or wait) the K and V copy of each live page
            of block ``blk`` of ``row`` into ``slot``."""
            first_page = first_page_of(row) + blk * block
            copies(row, first_page, jnp.minimum(
                block, pl.cdiv(len_ref[row], page_size) - first_page),
                slot, do)

        def start(row, blk, slot):
            block_copies(row, blk, slot, lambda c: c.start())

        def wait(row, blk, slot):
            block_copies(row, blk, slot, lambda c: c.wait())

        def attend_pages(slot, p, n, first_token):
            """Fold ``n`` (static) pages of ``slot`` from page ``p`` on
            into the row's softmax state."""
            tokens = n * page_size
            k = k_buf[slot, pl.ds(p, n)].astype(jnp.float32).reshape(
                tokens, kv_heads, d)
            v = v_buf[slot, pl.ds(p, n)].astype(jnp.float32).reshape(
                tokens, kv_heads, dv)
            if quantized:
                # dequantize in VMEM: one SMEM scalar per page row
                k = jnp.stack([k[t] * ks_ref[0, 0, first_token + t]
                               for t in range(tokens)])
                v = jnp.stack([v[t] * vs_ref[0, 0, first_token + t]
                               for t in range(tokens)])
            token = jax.lax.broadcasted_iota(
                jnp.int32, (tokens, kv_heads, 1), 0)
            valid = token < ctx - first_token
            if windowed:
                valid = valid & (token >= lo - first_token)
            for g in range(group):
                qb = q_ref[0, g].astype(jnp.float32)  # [kvh, d]
                s = jnp.sum(qb[None] * k, axis=-1,
                            keepdims=True) * scale
                s = jnp.where(valid, s, _MASK_VALUE)  # [tokens, kvh, 1]
                m_prev = m_ref[g, :, :1]              # [kvh, 1]
                l_prev = l_ref[g, :, :1]
                m_new = jnp.maximum(m_prev, jnp.max(s, axis=0))
                alpha = jnp.exp(m_prev - m_new)
                p_ = jnp.exp(s - m_new[None])
                l_new = alpha * l_prev + jnp.sum(p_, axis=0)
                acc_ref[g] = acc_ref[g] * alpha + jnp.sum(p_ * v,
                                                          axis=0)
                m_ref[g] = jnp.broadcast_to(m_new, m_ref.shape[1:])
                l_ref[g] = jnp.broadcast_to(l_new, l_ref.shape[1:])

        def attend_latent(slot, p, n, first_token, fetched):
            """Fold ``n`` (static) pages of ``slot`` from page ``p`` on, of
            which the first ``fetched`` tokens were copied: every head's
            query row against the rows as they lie, then the probabilities
            against the first ``dv`` columns of THE SAME rows."""
            tokens = n * page_size
            exact = jax.lax.Precision.HIGHEST \
                if k_pages.dtype == jnp.float32 else None
            k = k_buf[slot, pl.ds(p, n)].reshape(tokens, d)
            # what this block's copies did not write is whatever the slot
            # held: keep it out of both products
            k = jnp.where(jax.lax.broadcasted_iota(
                jnp.int32, (tokens, 1), 0) < fetched, k, jnp.zeros_like(k))
            s = jax.lax.dot_general(
                q_ref[0].astype(k_pages.dtype), k, (((1,), (1,)), ((), ())),
                precision=exact, preferred_element_type=jnp.float32) * scale
            token = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            valid = token < ctx - first_token
            if windowed:
                valid = valid & (token >= lo - first_token)
            s = jnp.where(valid, s, _MASK_VALUE)
            m_prev = m_ref[:, :1]
            l_prev = l_ref[:, :1]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p_ = jnp.where(valid, jnp.exp(s - m_new), 0.0)
            l_new = alpha * l_prev + jnp.sum(p_, axis=1, keepdims=True)
            acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
                p_.astype(k_pages.dtype), k[:, :dv],
                (((1,), (0,)), ((), ())), precision=exact,
                preferred_element_type=jnp.float32)
            m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
            l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

        def attend_pages_on_mxu(slot, p, n, first_token, fetched):
            """The same fold as two products on the MXU, for many query
            heads a K/V head: ``n`` (static) pages of ``slot`` from page
            ``p`` on, of which the first ``fetched`` tokens were copied. The
            rows' ``group x kv_heads`` query rows against ALL ``tokens x
            kv_heads`` key rows of the pages as they lie, read in the pool's
            type (one product, the entries of another K/V head masked: an
            eighth of it is used at 8 K/V heads, and the MXU has it to
            spare: what it charges is a key row loaded, once either way),
            the softmax state ``[group x kv_heads]`` rows wide, and the
            probabilities, rounded to the pool's type, against the value
            rows (bf16 pages: one pass each, float32 sums). A fold is one
            chain of product, softmax and product, each waiting for the one
            before: at 64 tokens a fold that latency was the walk's time,
            and a K/V head at a time is eight such chains (my chip runs,
            PR 41), so a fold is as long as ``_MXU_SCORE_BYTES`` lets it."""
            tokens = n * page_size
            rows = group * kv_heads
            cols = tokens * kv_heads
            exact = jax.lax.Precision.HIGHEST \
                if k_pages.dtype == jnp.float32 else None
            qb = q_ref[0].astype(jnp.float32).reshape(rows, d) \
                .astype(k_pages.dtype)
            kb = k_buf.at[slot, pl.ds(p, n)].reshape(cols, d)[...]
            vb = v_buf.at[slot, pl.ds(p, n)].reshape(cols, dv)[...]
            # what this block's copies did not write is whatever the slot
            # held: keep it out of the second product
            vb = jnp.where(jax.lax.broadcasted_iota(
                jnp.int32, (cols, 1), 0) < fetched * kv_heads, vb,
                jnp.zeros_like(vb))
            s = jax.lax.dot_general(
                qb, kb, (((1,), (1,)), ((), ())), precision=exact,
                preferred_element_type=jnp.float32) * scale  # [rows, cols]
            row = jax.lax.broadcasted_iota(jnp.int32, (rows, cols), 0)
            col = jax.lax.broadcasted_iota(jnp.int32, (rows, cols), 1)
            token = col // kv_heads
            valid = (row % kv_heads == col % kv_heads) \
                & (token < ctx - first_token)
            if windowed:
                valid = valid & (token >= lo - first_token)
            s = jnp.where(valid, s, _MASK_VALUE)
            m_prev = m_ref[...].reshape(rows, _LANES)[:, :1]
            l_prev = l_ref[...].reshape(rows, _LANES)[:, :1]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p_ = jnp.where(valid, jnp.exp(s - m_new), 0.0)
            l_new = alpha * l_prev + jnp.sum(p_, axis=1, keepdims=True)
            acc = acc_ref[...].reshape(rows, dv) * alpha + jax.lax.dot_general(
                p_.astype(k_pages.dtype), vb, (((1,), (0,)), ((), ())),
                precision=exact, preferred_element_type=jnp.float32)
            acc_ref[...] = acc.reshape(group, kv_heads, dv)
            m_ref[...] = jnp.broadcast_to(m_new, (rows, _LANES)).reshape(
                m_ref.shape)
            l_ref[...] = jnp.broadcast_to(l_new, (rows, _LANES)).reshape(
                l_ref.shape)

        @pl.when(ctx == 0)
        def _empty():                     # empty slot → a zero row
            o_ref[...] = jnp.zeros_like(o_ref)

        @pl.when(ctx > 0)
        def _attend():
            @pl.when(r == meta_ref[1])
            def _first_live_row():        # nobody prefetched for it
                slot_ref[0] = 0
                start(r, 0, 0)

            acc_ref[...] = jnp.zeros_like(acc_ref)
            if sunk:    # the sink is the softmax's start state
                m_ref[...] = sink_ref[...]
                l_ref[...] = jnp.ones_like(l_ref)
            else:
                m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
                l_ref[...] = jnp.zeros_like(l_ref)

            def attend_block(blk, slot):
                @pl.when(blk + 1 < n_blocks)
                def _next_block():
                    start(r, blk + 1, 1 - slot)

                @pl.when(blk + 1 == n_blocks)
                def _next_row():
                    nxt = next_ref[r]

                    @pl.when(nxt < rows)
                    def _():
                        start(nxt, 0, 1 - slot)

                wait(r, blk, slot)
                first_page = blk * block
                here = jnp.minimum(block, n_pages - first_page)

                def fold(n):
                    def body(i, p):
                        attend_pages(slot, p, n,
                                     (page0 + first_page + p) * page_size)
                        return p + n
                    return body

                def fold_whole(i, p):
                    (attend_latent if latent else attend_pages_on_mxu)(
                        slot, p, group_pages,
                        (page0 + first_page + p) * page_size,
                        (here - p) * page_size)
                    return p + group_pages

                if latent or on_mxu:
                    # whole groups only (``block`` is a multiple of one):
                    # the last one's unfetched pages are masked
                    jax.lax.fori_loop(0, pl.cdiv(here, group_pages),
                                      fold_whole, 0)
                    return 1 - slot
                # whole groups of pages first, the rest a page at a time
                p = jax.lax.fori_loop(0, here // group_pages,
                                      fold(group_pages), 0)
                if group_pages > 1:
                    jax.lax.fori_loop(0, here % group_pages, fold(1), p)
                return 1 - slot

            slot_ref[0] = jax.lax.fori_loop(0, n_blocks, attend_block,
                                            slot_ref[0])
            if latent:
                o_ref[0] = (acc_ref[...] / l_ref[:, :1]).astype(o_ref.dtype)
            else:
                o_ref[0] = (acc_ref[...] / l_ref[:, :, :1]).astype(
                    o_ref.dtype)

    hbm_spec = pl.BlockSpec(memory_space=pl.ANY)
    prefetch = [lens, tables, next_live, meta] \
        + ([starts] if windowed else [])
    if latent:
        return _latent_row_walk(
            kernel, prefetch, qg, k_pages, block=block, dv=dv,
            interpret=interpret)
    q_spec = pl.BlockSpec((1, group, kv_heads, d),
                          lambda r, *_: (r, 0, 0, 0))
    in_specs = [q_spec, hbm_spec, hbm_spec]
    operands = prefetch + [qg, k_pages, v_pages]
    if quantized:
        # the scale rows of each row's table, [rows, 1, max tokens]: a
        # gather of 4 bytes a token done by XLA beside the kernel, one
        # row of it in SMEM a grid step (scalar reads)
        scale_spec = pl.BlockSpec(
            (1, 1, pages_per_seq * page_size), lambda r, *_: (r, 0, 0),
            memory_space=pltpu.SMEM)

        def row_scales(scales):
            return jnp.take(scales[layer].astype(jnp.float32), tables,
                            axis=0).reshape(
                rows, 1, pages_per_seq * page_size)

        in_specs += [scale_spec, scale_spec]
        operands += [row_scales(k_scales), row_scales(v_scales)]
    if sunk:
        # [group, kv_heads, lanes], as the softmax state lies: the logit of
        # query head ``kv * group + g`` at ``[g, kv]``
        in_specs.append(pl.BlockSpec((group, kv_heads, _LANES),
                                     lambda r, *_: (0, 0, 0)))
        operands.append(jnp.broadcast_to(
            sinks.astype(jnp.float32).reshape(kv_heads, group).T[..., None],
            (group, kv_heads, _LANES)))

    def page_buffer(width):
        return pltpu.VMEM((2, block, page_size, kv_heads, width),
                          k_pages.dtype)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),
        grid=(rows,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, group, kv_heads, dv),
                               lambda r, *_: (r, 0, 0, 0)),
        scratch_shapes=[
            page_buffer(d), page_buffer(dv),
            pltpu.SemaphoreType.DMA((2, 2)),      # (K | V, slot)
            pltpu.SMEM((1,), jnp.int32),          # slot of the block due
            pltpu.VMEM((group, kv_heads, dv), jnp.float32),
            pltpu.VMEM((group, kv_heads, _LANES), jnp.float32),
            pltpu.VMEM((group, kv_heads, _LANES), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((rows, group, kv_heads, dv),
                                       q.dtype),
        # sequential: the page buffers, their semaphores and the slot
        # carry a prefetched block from one row's step into the next
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="paged_attention",
    )(*operands)
    return out.transpose(0, 2, 1, 3).reshape(rows, n_heads, dv)


def _latent_row_walk(kernel, prefetch, q, pages, *, block, dv, interpret):
    """The row walk's ``pallas_call`` over a latent pool: ONE page buffer
    (two slots), the softmax state a query head."""
    rows, n_heads, d = q.shape
    page_size = pages.shape[2]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),
        grid=(rows,),
        in_specs=[pl.BlockSpec((1, n_heads, d), lambda r, *_: (r, 0, 0)),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((1, n_heads, dv), lambda r, *_: (r, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, block, page_size, d), pages.dtype),
            pltpu.SemaphoreType.DMA((1, 2)),
            pltpu.SMEM((1,), jnp.int32),          # slot of the block due
            pltpu.VMEM((n_heads, dv), jnp.float32),
            pltpu.VMEM((n_heads, _LANES), jnp.float32),
            pltpu.VMEM((n_heads, _LANES), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((rows, n_heads, dv), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="paged_attention",
    )(*prefetch, q, pages)


# rows of a query tile. Not from the query heads: the pool's counter
# (``PagePool.pages_touched``) reckons the same tiles from what a pool
# knows, and that is the rows alone
_TILE_ROWS = 32


def chunk_tile_rows(n_chunk: int) -> int:
    """``QB``, the rows of a query tile, for a chunk of ``n_chunk`` packed
    prompt rows: ``_TILE_ROWS``, or the chunk rounded up to whole sublanes
    where it is shorter. At 32 rows a tile's float32 state (sums and
    softmax state, ``QB x heads x (d + 2 x 128)``) is 3.5 MB for 72 heads
    of 128, beside 4 MB of page buffers."""
    return min(_TILE_ROWS, -(-n_chunk // 8) * 8)


def chunk_tiles(new_seq, limits, starts, page_size: int, qb: int, xp=jnp):
    """THE arithmetic of query tiles, for the kernel's plan (``xp=jnp``, in
    the program) and for the pool's counter (``xp=numpy``, on the host).

    ``limits`` / ``starts`` [n] (n a multiple of ``qb``): a chunk's packed
    prompt rows, row r attending positions ``starts[r] <= j < limits[r]``
    (limit 0: a padded row); ``new_seq`` [n] bool: row r is not of the
    sequence of row r - 1. A TILE is a run of live rows of one sequence
    inside one window of ``qb`` rows (rows ``w * qb .. (w + 1) * qb - 1``):
    a sequence boundary, a padded row and a window's edge each end one. It
    walks the pages from that of its lowest ``starts`` to that of its
    highest limit once.

    Returns ``(head, count, first_page, last_page)``, each [n] and read at
    a tile's FIRST row (``head``): how many rows it has and the pages
    ``first_page <= p < last_page`` it walks."""
    n = limits.shape[0]
    live = limits > 0
    after_live = xp.concatenate([xp.zeros((1,), bool), live[:-1]])
    head = live & (new_seq | ~after_live | (xp.arange(n) % qb == 0))
    tile = xp.where(live, xp.cumsum(head), 0).reshape(-1, qb)
    # [windows, qb, qb]: row i and row j of a window are of one tile
    mates = (tile[:, :, None] == tile[:, None, :]) & (tile[:, :, None] > 0)
    top = xp.where(mates, limits.reshape(-1, 1, qb), 0).max(-1)
    low = xp.where(mates, starts.reshape(-1, 1, qb),
                   xp.iinfo(xp.int32).max).min(-1)
    return (head, mates.sum(-1).reshape(n),
            (low // page_size).reshape(n), (-(-top // page_size)).reshape(n))


def _head_rows(buf, slot, kv_heads: int, tokens: int):
    """The rows of each K/V head out of a page buffer slot, as the pages
    lie (``[pages, page_size, kv_heads, d]``: a head's rows are every
    ``kv_heads``-th of the flattened ``[tokens x kv_heads, d]``): a
    strided load a head. bf16 rows lie two to a 32-bit sublane, so an
    even number of bf16 heads is loaded a PAIR at a time as 32-bit words
    and taken apart (a bf16 is the upper half of its float32). Yields
    ``(head, rows [tokens, d] in the pool's type)``."""
    d = buf.shape[-1]
    flat = buf.at[slot].reshape(tokens * kv_heads, d)
    if buf.dtype == jnp.bfloat16 and kv_heads % 2 == 0:
        words = flat.bitcast(jnp.uint32)         # [tokens x kv_heads / 2, d]
        for pair in range(kv_heads // 2):
            w = words[pair::kv_heads // 2, :]
            for half, bits in ((0, w << 16), (1, w & jnp.uint32(0xFFFF0000))):
                yield 2 * pair + half, pltpu.bitcast(
                    bits, jnp.float32).astype(jnp.bfloat16)
    elif jnp.dtype(buf.dtype).itemsize == 4:
        for h in range(kv_heads):
            yield h, flat[h::kv_heads, :]
    else:   # a type with no strided load of its own: by way of float32
        rows = buf[slot].astype(jnp.float32).reshape(tokens, kv_heads, d)
        for h in range(kv_heads):
            yield h, rows[:, h].astype(buf.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "interpret", "block",
                                             "qb", "value_dim"))
def _paged_attention_chunk_call(q, k_pages, v_pages, block_tables,
                                context_lens, layer, starts=None, sinks=None,
                                *, scale, interpret, block, qb,
                                value_dim=None):
    """The QUERY-TILE path of :func:`paged_attention_kernel`: ``q`` holds
    packed prompt rows only (the rows of one sequence contiguous and in
    order), over the stacked unquantized pool with a traced ``layer``.
    Jitted for the same reason as the row walk: one trace a program.

    The same algorithm as the row walk (online-softmax attention over the
    live pages, a block of pages a step through two VMEM slots, the next
    block in flight while one is folded) with a block of QUERIES where the
    row walk has one row. The plan (:func:`chunk_tiles`, a few small
    fusions beside the kernel) cuts the rows into tiles of up to ``qb``
    rows of one sequence. The grid is the windows of ``qb`` rows; a window
    walks each of its tiles (one, unless a sequence ends inside it). A
    tile fetches each page from its lowest ``starts`` to its highest limit
    ONCE and folds a block for all its rows: a K/V head's rows are taken
    out of the pages as they lie (:func:`_head_rows`) and multiplied with
    the ``qb x group`` query rows of that head on the MXU, the mask a row
    (``starts[row] <= token < limits[row]``: causal inside the chunk as in
    the row walk), float32 scores and softmax state a query row, the
    probabilities rounded to the pool's type for the second product,
    float32 sums. A row outside the tile at work (another sequence's, a
    padded one) is masked whole and its output left alone; a padded row's
    output is zero.

    A latent pool (``v_pages`` None): the one "K/V head" is the page's rows
    as they lie, all ``qb x heads`` query rows of a tile against them, the
    values their first ``value_dim`` columns; ``block`` is then one group of
    ``_LATENT_GROUP_TOKENS`` (a tile's scores are ``qb x heads`` rows
    wide).

    ``sinks`` [heads]: a tile's start state, as in the row walk (``m`` a
    query row its head's logit, ``l`` 1). V pages of another width than K's:
    a buffer each, the sums as wide as V. KEYS WIDER THAN A LANE TILE (whole
    tiles: 256): :func:`_head_rows`' strided loads want a buffer whose rows
    are one lane tile, so the K page buffer is one buffer a lane tile, a
    page's copy one a tile out of the same rows, and a head's key rows are
    its tiles side by side again."""
    windowed = starts is not None
    sunk = sinks is not None
    latent = v_pages is None
    rows, n_heads, d = q.shape
    if latent:
        page_size, kv_heads, dv = k_pages.shape[2], 1, value_dim
    else:
        _, _, page_size, kv_heads, _ = k_pages.shape
        dv = v_pages.shape[-1]
    pages_per_seq = block_tables.shape[1]
    group = n_heads // kv_heads
    n_win = -(-rows // qb)
    # lane tiles of a key, each with a page buffer of its own; 1: one buffer
    k_cols = d // _LANES if not latent and d > _LANES and d % _LANES == 0 \
        else 1
    tile_rows = qb * group            # query rows a K/V head a tile
    tokens = block * page_size
    exact = jax.lax.Precision.HIGHEST \
        if k_pages.dtype == jnp.float32 else None

    pad = n_win * qb - rows
    tables = jnp.clip(block_tables, 0).astype(jnp.int32)
    lens = jnp.pad(jnp.clip(context_lens.astype(jnp.int32), 0,
                            pages_per_seq * page_size), (0, pad))
    lows = jnp.zeros_like(lens) if not windowed else jnp.pad(
        jnp.clip(starts.astype(jnp.int32), 0, lens[:rows]), (0, pad))
    new_seq = jnp.pad(jnp.any(tables[1:] != tables[:-1], axis=1), (1, pad),
                      constant_values=True)
    head, count, first_page, last_page = chunk_tiles(
        new_seq, lens, lows, page_size, qb)
    # next_tile[r]: the first row of the first tile after row r (``rows``
    # padded when there is none); the prefetch across tiles follows it
    row_ids = jnp.arange(n_win * qb, dtype=jnp.int32)
    tile_from = jax.lax.cummin(
        jnp.where(head, row_ids, n_win * qb), reverse=True)
    next_tile = jnp.concatenate(
        [tile_from[1:], jnp.full((1,), n_win * qb, jnp.int32)])
    meta = jnp.stack([layer, tile_from[0]])

    # [windows, kv_heads, group x qb, d]: a K/V head's query rows of a
    # window together, a group row's qb rows contiguous
    qt = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(
        n_win, qb, kv_heads, group, d).transpose(0, 2, 3, 1, 4).reshape(
        n_win, kv_heads, tile_rows, d)

    def kernel(head_ref, count_ref, lo_ref, hi_ref, next_ref, meta_ref,
               tbl_ref, q_ref, lim_ref, low_ref, k_hbm, *rest):
        if latent:
            v_hbm = v_buf = None
            o_ref, k_buf, sems, slot_ref, acc_ref, m_ref, l_ref = rest
        else:
            v_hbm, rest = rest[0], rest[1:]
            if sunk:
                sink_ref, rest = rest[0], rest[1:]
            o_ref, rest = rest[0], rest[1:]
            k_buf = rest[0] if k_cols == 1 else tuple(rest[:k_cols])
            (v_buf, sems, slot_ref, acc_ref, m_ref,
             l_ref) = rest[k_cols:]
        w = pl.program_id(0)

        def head_rows(slot):
            """``(head, its key rows, its value rows)`` of a buffer slot."""
            if latent:
                rows_ = k_buf[slot].reshape(tokens, d)
                yield 0, rows_, rows_[:, :dv]
                return
            columns = k_buf if k_cols > 1 else (k_buf,)
            for tiles, (h, vh) in zip(
                    zip(*(_head_rows(column, slot, kv_heads, tokens)
                          for column in columns)),
                    _head_rows(v_buf, slot, kv_heads, tokens)):
                yield h, (tiles[0][1] if k_cols == 1 else jnp.concatenate(
                    [rows_ for _, rows_ in tiles], axis=1)), vh

        copies = _page_copier(meta_ref, tbl_ref, k_hbm, v_hbm, k_buf, v_buf,
                              sems)

        def block_copies(row, blk, slot, do):
            """``do`` (start or wait) the K and V copy of each live page
            of block ``blk`` of the tile whose first row is ``row``."""
            first_page = lo_ref[row] + blk * block
            copies(row, first_page,
                   jnp.minimum(block, hi_ref[row] - first_page), slot, do)

        def start(row, blk, slot):
            block_copies(row, blk, slot, lambda c: c.start())

        def wait(row, blk, slot):
            block_copies(row, blk, slot, lambda c: c.wait())

        def per_row(column):
            """[qb, 1] a row of the window -> [tile_rows, 1] a query row of
            a K/V head (group rows of qb)."""
            return jnp.concatenate([column] * group, axis=0)

        def tile(row):
            n_pages = hi_ref[row] - lo_ref[row]
            n_blocks = pl.cdiv(n_pages, block)
            at = jax.lax.broadcasted_iota(jnp.int32, (qb, 1), 0) + w * qb
            mine = (at >= row) & (at < row + count_ref[row])
            limit = per_row(jnp.where(mine, lim_ref[0], 0))
            lower = per_row(low_ref[0]) if windowed else None

            @pl.when(row == meta_ref[1])
            def _first_tile():            # nobody prefetched for it
                slot_ref[0] = 0
                start(row, 0, 0)

            acc_ref[...] = jnp.zeros_like(acc_ref)
            if sunk:    # the sink is the softmax's start state, a query row
                for h in range(kv_heads):
                    m_ref[h] = jnp.concatenate([
                        jnp.broadcast_to(sink_ref[h, g:g + 1, :],
                                         (qb, _LANES))
                        for g in range(group)], axis=0)
                l_ref[...] = jnp.ones_like(l_ref)
            else:
                m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
                l_ref[...] = jnp.zeros_like(l_ref)

            def attend_block(blk, slot):
                @pl.when(blk + 1 < n_blocks)
                def _next_block():
                    start(row, blk + 1, 1 - slot)

                @pl.when(blk + 1 == n_blocks)
                def _next_tile():
                    nxt = next_ref[row]

                    @pl.when(nxt < n_win * qb)
                    def _():
                        start(nxt, 0, 1 - slot)

                wait(row, blk, slot)
                first_token = (lo_ref[row] + blk * block) * page_size
                token = first_token + jax.lax.broadcasted_iota(
                    jnp.int32, (tile_rows, tokens), 1)
                valid = token < limit
                if windowed:
                    valid = valid & (token >= lower)
                # what the copies of this block did not write is whatever
                # the slot held: keep it out of the second product
                fetched = jax.lax.broadcasted_iota(
                    jnp.int32, (tokens, 1), 0) < jnp.minimum(
                    block, n_pages - blk * block) * page_size
                for h, kh, vh in head_rows(slot):
                    qh = q_ref[0, h].astype(k_pages.dtype)   # [tile_rows, d]
                    s = jax.lax.dot_general(
                        qh, kh, (((1,), (1,)), ((), ())), precision=exact,
                        preferred_element_type=jnp.float32) * scale
                    s = jnp.where(valid, s, _MASK_VALUE)
                    m_prev = m_ref[h][:, :1]
                    l_prev = l_ref[h][:, :1]
                    m_new = jnp.maximum(
                        m_prev, jnp.max(s, axis=1, keepdims=True))
                    alpha = jnp.exp(m_prev - m_new)
                    p_ = jnp.where(valid, jnp.exp(s - m_new), 0.0)
                    l_new = alpha * l_prev + jnp.sum(p_, axis=1,
                                                     keepdims=True)
                    vh = jnp.where(fetched, vh, jnp.zeros_like(vh))
                    acc_ref[h] = acc_ref[h] * alpha + jax.lax.dot_general(
                        p_.astype(k_pages.dtype), vh,
                        (((1,), (0,)), ((), ())), precision=exact,
                        preferred_element_type=jnp.float32)
                    m_ref[h] = jnp.broadcast_to(m_new, m_ref.shape[1:])
                    l_ref[h] = jnp.broadcast_to(l_new, l_ref.shape[1:])
                return 1 - slot

            slot_ref[0] = jax.lax.fori_loop(0, n_blocks, attend_block,
                                            slot_ref[0])
            live = limit > 0              # [tile_rows, 1]: this tile's rows
            total = jnp.where(live, l_ref[...][:, :, :1], 1.0)
            o_ref[0] = jnp.where(live, acc_ref[...] / total,
                                 o_ref[0].astype(jnp.float32)).astype(
                o_ref.dtype)

        o_ref[...] = jnp.zeros_like(o_ref)     # a padded row: a zero row

        def row_of_window(i, carry):
            row = w * qb + i

            @pl.when(head_ref[row] == 1)
            def _():
                tile(row)

            return carry

        jax.lax.fori_loop(0, qb, row_of_window, 0)

    q_spec = pl.BlockSpec((1, kv_heads, tile_rows, d),
                          lambda w, *_: (w, 0, 0, 0))
    o_spec = pl.BlockSpec((1, kv_heads, tile_rows, dv),
                          lambda w, *_: (w, 0, 0, 0))
    row_spec = pl.BlockSpec((1, qb, 1), lambda w, *_: (w, 0, 0))
    hbm_spec = pl.BlockSpec(memory_space=pl.ANY)
    prefetch = [head.astype(jnp.int32), count.astype(jnp.int32),
                first_page.astype(jnp.int32), last_page.astype(jnp.int32),
                next_tile, meta, tables]
    if latent:
        pools = [k_pages]
        page_buffers = [pltpu.VMEM((2, block, page_size, d), k_pages.dtype)]
    else:
        pools = [k_pages, v_pages]
        page_buffers = [pltpu.VMEM((2, block, page_size, kv_heads, width),
                                   k_pages.dtype)
                        for width in [d // k_cols] * k_cols + [dv]]
    sink_specs, sink_operands = [], []
    if sunk:
        # [kv_heads, group, lanes]: the logit of query head ``kv * group +
        # g`` at ``[kv, g]``; a tile spreads it over its qb rows
        sink_specs = [pl.BlockSpec((kv_heads, group, _LANES),
                                   lambda w, *_: (0, 0, 0))]
        sink_operands = [jnp.broadcast_to(
            sinks.astype(jnp.float32).reshape(kv_heads, group, 1),
            (kv_heads, group, _LANES))]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),
        grid=(n_win,),
        in_specs=[q_spec, row_spec, row_spec] + [hbm_spec] * len(pools)
        + sink_specs,
        out_specs=o_spec,
        scratch_shapes=page_buffers + [
            pltpu.SemaphoreType.DMA((len(pools), 2)),     # (K | V, slot)
            pltpu.SMEM((1,), jnp.int32),          # slot of the block due
            pltpu.VMEM((kv_heads, tile_rows, dv), jnp.float32),
            pltpu.VMEM((kv_heads, tile_rows, _LANES), jnp.float32),
            pltpu.VMEM((kv_heads, tile_rows, _LANES), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(qt.shape[:-1] + (dv,), q.dtype),
        # sequential: the page buffers, their semaphores and the slot
        # carry a prefetched block from one tile into the next
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            **({"vmem_limit_bytes": _LATENT_TILE_VMEM_BYTES} if latent
               else {})),
        interpret=interpret,
        name="paged_attention_chunk",
    )(*prefetch, qt, lens.reshape(n_win, qb, 1), lows.reshape(n_win, qb, 1),
      *pools, *sink_operands)
    return out.reshape(n_win, kv_heads, group, qb, dv).transpose(
        0, 3, 1, 2, 4).reshape(n_win * qb, n_heads, dv)[:rows]


def ragged_paged_attention(q, kv_k: KVStore, kv_v: KVStore,
                           token_tables, token_lens,
                           scale: Optional[float] = None,
                           impl: str = "xla", layer=None, starts=None,
                           n_chunk: int = 0,
                           value_dim: Optional[int] = None, sinks=None):
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
    serve both in one dispatch.

    Pure-functional and trace-safe by contract: every input may be a
    traced value, so the op is callable from inside a ``lax.scan``
    body — the engine's fused slab carries the (possibly quantized)
    pool in its :class:`DecodeCarry` and calls this per tick.

    ``layer``: with it, ``kv_k`` / ``kv_v`` are the engine's STACKED
    ``[L, num_pages, ...]`` stores and the call attends that layer of
    them (an int or a traced scalar); without it they are one layer's
    pages. The kernel reads the layer's pages where they lie in the
    stacked pool; the gathered paths take the layer's view first
    (:func:`kv_layer`).

    ``starts`` [T] (None = no lower bound, today's behaviour bit for
    bit): token t attends positions ``starts[t] <= j < token_lens[t]``,
    a sliding window's rows. All three paths take it; the kernel fetches
    no page that lies wholly before a row's ``starts``.

    ``n_chunk`` (a static Python int; 0 = none, today's program text):
    the first ``n_chunk`` tokens are PACKED PROMPT ROWS (``RaggedRows``:
    the rows of one sequence contiguous and in order). Only the kernel
    reads it: it sends them through query tiles, each page of a sequence
    fetched once a tile of rows and not once a row
    (:func:`paged_attention_kernel`). The result is that of the row walk
    to rounding; the gathered paths ignore it.

    ``impl``: ``"xla"`` (gather of every table entry + dense masked
    softmax, f32 accumulate: the path off the TPU), ``"pallas"``
    (:func:`paged_attention_kernel`: a row's live pages streamed out of
    the pool a block a step, int8 dequantized in VMEM), or
    ``"reference"`` (:func:`ragged_paged_attention_reference` —
    full-f32 exactness baseline, kept callable for the int8 tolerance
    tests).

    A LATENT pool (``kv_v`` None, ``value_dim`` given; ``page_pool.
    CacheGroup.value_dim``): ``kv_k`` is ONE store ``[(L,) num_pages,
    page_size, width]``, a token's row the key of every query head and its
    first ``value_dim`` columns the value: ``q`` [T, heads, width] ->
    [T, heads, value_dim]. All three paths take it; none reads a second
    pool.

    K AND V OF DIFFERENT WIDTHS (``kv_v``'s last dimension is not
    ``kv_k``'s): ``q`` [T, heads, dk] -> [T, heads, dv], on all three paths.

    ``sinks`` [heads] float32 (None = none, today's program text): query
    head ``h`` carries a logit ``sinks[h]`` that joins its softmax's
    denominator and has no value: ``o_h = sum_j e^{s_hj - m} v_j /
    (e^{sinks[h] - m} + sum_j e^{s_hj - m})``, ``m`` the largest of all of
    them. All three paths take it, with ``starts`` and ``n_chunk`` or
    without. An int8 pool with a sink or with unequal widths, and a latent
    pool with a sink, are refused by name (:func:`_refuse_unserved`)."""
    if kv_v is None:
        _refuse_unserved(True, False, sinks, None)
        return _latent_attention(q, kv_k, token_tables, token_lens, scale,
                                 impl, layer, starts, n_chunk, value_dim)
    if layer is not None and impl != "pallas":
        kv_k, kv_v = kv_layer(kv_k, layer), kv_layer(kv_v, layer)
    kp, ks = _split_kv(kv_k)
    vp, vs = _split_kv(kv_v)
    _refuse_unserved(False, ks is not None, sinks,
                     (kp.shape[-1], vp.shape[-1]))
    if impl == "pallas":
        return paged_attention_kernel(q, kp, vp, token_tables,
                                      token_lens, layer=layer,
                                      scale=scale, k_scales=ks,
                                      v_scales=vs, starts=starts,
                                      n_chunk=n_chunk, sinks=sinks)
    if impl == "reference":
        return ragged_paged_attention_reference(
            q, kv_k, kv_v, token_tables, token_lens,
            scale=scale, starts=starts, sinks=sinks).astype(q.dtype)
    if impl != "xla":
        raise ValueError(f"unknown impl {impl!r}")
    d = q.shape[-1]
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    # the K=1 case of the gathered core, with limit = token_lens
    # DIRECTLY (a single cached token — limit 1 — still attends)
    out = _gathered_attention(q[:, None], kp, vp, token_tables,
                              token_lens[:, None], scale,
                              k_scales=ks, v_scales=vs,
                              start=_column(starts), sinks=sinks)
    return out[:, 0]


def _column(starts):
    return None if starts is None else starts[:, None]


def _latent_attention(q, pages, token_tables, token_lens, scale, impl,
                      layer, starts, n_chunk, value_dim):
    """:func:`ragged_paged_attention` over a latent pool (no V pages)."""
    if isinstance(pages, QuantizedKV) or value_dim is None:
        raise ValueError("a pool without V pages is a latent pool: it "
                         "needs value_dim and has no int8 form")
    if impl == "pallas":
        return paged_attention_kernel(
            q, pages, None, token_tables, token_lens, layer=layer,
            scale=scale, starts=starts, n_chunk=n_chunk,
            value_dim=value_dim)
    if impl not in ("xla", "reference"):
        raise ValueError(f"unknown impl {impl!r}")
    if layer is not None:
        pages = kv_layer(pages, layer)
    scale = scale if scale is not None else 1.0 / math.sqrt(q.shape[-1])
    # the gathered core with one K/V "head": the rows, and their first
    # value_dim columns (a slice of the gathered rows, not a second pool)
    out = _gathered_attention(
        (q.astype(jnp.float32) if impl == "reference" else q)[:, None],
        pages[:, :, None], None, token_tables, token_lens[:, None], scale,
        start=_column(starts), value_dim=value_dim)
    return out[:, 0].astype(q.dtype)


def ragged_paged_attention_reference(q, kv_k: KVStore, kv_v: KVStore,
                                     token_tables, token_lens,
                                     scale: Optional[float] = None,
                                     starts=None, sinks=None):
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
                              k_scales=ks, v_scales=vs,
                              start=_column(starts), sinks=sinks)
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
                        scale, k_scales=None, v_scales=None, start=None,
                        value_dim=None, sinks=None):
    """Shared decode-attention core: gather the block table's pages,
    dequantize (optional per-row scales), expand GQA, masked fp32
    softmax. q [B, K, H, d]; limit [B, K] = attendable cached
    positions per query (0 → zero output row); ``start`` [B, K] their
    lower bound (None: 0). ``v_pages`` None: the values are the first
    ``value_dim`` columns of the gathered key rows. The values may be of
    another width than the keys; ``sinks`` [H]: one more logit a head in the
    softmax's denominator, with no value."""
    b, kq, n_heads, d = q.shape
    _, page_size, kv_heads, _ = k_pages.shape
    pages_per_seq = block_tables.shape[1]

    L = pages_per_seq * page_size
    with jax.named_scope("kv_gather"):
        tables = jnp.clip(block_tables, 0)             # [B, P]
        k = jnp.take(k_pages, tables, axis=0)          # [B, P, ps, KVH, d]
        v = k[..., :value_dim] if v_pages is None \
            else jnp.take(v_pages, tables, axis=0)
        if k_scales is not None:
            # int8 pool: dequantize the gathered rows (scale per page
            # row)
            k = k.astype(jnp.float32) * \
                jnp.take(k_scales, tables, axis=0)[..., None, None]
            v = v.astype(jnp.float32) * \
                jnp.take(v_scales, tables, axis=0)[..., None, None]
        k = k.reshape(b, L, kv_heads, d)
        v = v.reshape(b, L, kv_heads, v.shape[-1])
        if n_heads != kv_heads:
            rep = n_heads // kv_heads
            k = jnp.repeat(k, rep, axis=2)
            v = jnp.repeat(v, rep, axis=2)

    with jax.named_scope("attn_scores"):
        logits = jnp.einsum("bqhd,blhd->bhql", q.astype(jnp.float32),
                            k.astype(jnp.float32)) * scale   # [B,H,K,L]
        mask = jnp.arange(L)[None, None, :] < limit[:, :, None]  # [B,K,L]
        if start is not None:
            mask = mask & (jnp.arange(L)[None, None, :]
                           >= start[:, :, None])
        logits = jnp.where(mask[:, None], logits, -jnp.inf)
        if sinks is None:
            p = jax.nn.softmax(logits, axis=-1)
        else:       # a column more in the softmax, dropped from the sum
            sink = jnp.broadcast_to(
                sinks.astype(jnp.float32)[None, :, None, None],
                logits.shape[:-1] + (1,))
            p = jax.nn.softmax(jnp.concatenate([logits, sink], -1),
                               axis=-1)[..., :-1]
        # fully-masked rows (limit 0, e.g. a freed slot): zeros, not NaN
        p = jnp.where(limit[:, None, :, None] > 0, p, 0.0)
        out = jnp.einsum("bhql,blhd->bqhd", p, v.astype(jnp.float32))
        return out.astype(q.dtype)
