"""The paged K/V pool of an ``LLMEngine``, as a list of CACHE GROUPS.

A model names the K/V it keeps with ``kv_cache_spec()``: a bare triple
``(cache layers, kv_heads, head_dim)`` (one group, every model until one
mixed window and full attention), or a list of :class:`CacheGroup`. A group
is a set of cache layers that share a page shape AND a page lifetime: its
own stacked ``[layers, pages, page_size, kv_heads, head_dim]`` K and V
arrays, its own free list, its own block table ``[slots, pages_per_seq]``
(page 0 = the scratch page / "not allocated"). A LATENT group
(``CacheGroup.value_dim``) keeps ONE array and no V: a token's row
``[head_dim]`` is the key of every query head and its first ``value_dim``
columns are the value, so its pages are ``[layers, pages, page_size,
head_dim]`` and ``v_pages`` is None. A group whose VALUES ARE OF ANOTHER
WIDTH than its keys (``CacheGroup.v_head_dim``) keeps its V array ``[...,
kv_heads, v_head_dim]`` beside K's ``[..., kv_heads, head_dim]``; its bytes
are counted as stored, K and V each at its own width. A group WITH A WINDOW keeps,
a sequence, only the pages a row can still attend: once a page lies wholly
behind ``next position - window`` it goes back to the free list
(:meth:`PagePool.release_behind`), so a slot never holds more than
:attr:`GroupPool.ring` pages of it whatever the sequence's length.

Everything here is host control plane (numpy tables, Python lists) except
the arrays themselves, which the engine's programs take, donate and return.
The allocator is written once, over groups: a model with one group and no
window is the list of one and runs the same code.

What has NOT moved here (ROADMAP C7): the recurrent state rows, the prefix
cache (it keys pages of the one group of a model without a window; the pool
only asks it for an evictable page and whether a page is shared), and the
``kv_pages/v1`` export / import.
"""

from __future__ import annotations

from typing import Any, Dict, List, NamedTuple, Optional, Sequence

import jax.numpy as jnp
import numpy as np

from ..ops.paged_attention import (QuantizedKV, chunk_tile_rows,
                                   chunk_tiles, kv_nbytes, kv_scale_nbytes,
                                   kv_zeros)


class CacheGroup(NamedTuple):
    """One entry of a model's ``kv_cache_spec()`` list. ``window``: a row
    at position ``p`` attends positions ``j`` with ``p - j < window`` only
    (None: the whole sequence). ``value_dim`` (None: a group of K and V
    pages, ``kv_heads x head_dim`` each a token): a group WITHOUT A V and
    without a head axis. A token's cached row is ``head_dim`` values wide
    (as stored, padding included), every query head attends the same row,
    and the value is the row's first ``value_dim`` columns: what a latent
    (compressed) attention caches. ``kv_heads`` is then 1. ``v_head_dim``
    (None: as wide as ``head_dim``): the last dimension of the V array where
    a head's value is of another width than its key; ``head_dim`` is then
    the key's, AS STORED (a model pads it to whole lanes itself). ``sink``:
    the group's layers carry a softmax sink a query head
    (``ragged_paged_attention(sinks=)``); the pool stores nothing for it,
    ``/statusz`` says so and an int8 pool is refused (:class:`NoInt8Form`)."""

    name: str
    layers: int
    kv_heads: int
    head_dim: int
    window: Optional[int] = None
    value_dim: Optional[int] = None
    v_head_dim: Optional[int] = None
    sink: bool = False


class NoInt8Form(ValueError):
    """An int8 pool was asked of a cache group no attention path serves
    quantized (a softmax sink, values of another width than the keys). The
    decision is the pool's; the engine says it by name
    (``CacheGroupUnsupported``, mechanism ``int8_pages``)."""


class ChunkRows(NamedTuple):
    """The PACKED PROMPT ROWS of one attention call, as its programs carry
    them (``RaggedRows.n_chunk``): ``seqs`` / ``limits`` ``[programs,
    rows]``, a row's sequence and limit (a padded row: -1 and 0). ``pool``
    names another pool's sequences (the draft's), so that they are not
    taken for this one's."""

    seqs: np.ndarray
    limits: np.ndarray
    pool: Any = None

    def live_rows(self) -> List[tuple]:
        """``(sequence, limit)`` of every row that is not padding."""
        return [(int(s) if self.pool is None else (self.pool, int(s)),
                 int(n))
                for s, n in zip(self.seqs.ravel(), self.limits.ravel())
                if n > 0]


def is_bare(spec) -> bool:
    return isinstance(spec[0], int)


def cache_groups(spec) -> List[CacheGroup]:
    """``kv_cache_spec()`` as a list of groups: a bare triple is the list
    of one, named ``"kv"``."""
    if is_bare(spec):
        return [CacheGroup("kv", *spec)]
    return [g if isinstance(g, CacheGroup) else CacheGroup(*g)
            for g in spec]


class GroupPool:
    """One group's arrays, free list and block table."""

    def __init__(self, group: CacheGroup, num_pages: int, page_size: int,
                 max_seqs: int, pages_per_seq: int, kv_dtype,
                 prefill_chunk: int):
        self.group = group
        self.page_size = page_size
        self.name, self.window = group.name, group.window
        self.ring: Optional[int] = None
        if group.window is not None:
            # what one slot can hold: the window, the chunk of prompt
            # rows being written in front of it, one page of misalignment
            self.ring = min(pages_per_seq, -(-(group.window + prefill_chunk)
                                             // page_size) + 1)
            num_pages = min(num_pages, max_seqs * self.ring + 1)
        self.num_pages = num_pages
        self.latent = group.value_dim is not None
        # a latent group: one array, no head axis, no V
        heads = () if self.latent else (group.kv_heads,)
        self.k_pages = kv_zeros((group.layers, num_pages, page_size)
                                + heads + (group.head_dim,), kv_dtype)
        if self.latent and (group.kv_heads != 1
                            or isinstance(self.k_pages, QuantizedKV)):
            raise ValueError(
                f"cache group {group.name!r}: a group without a V has "
                f"kv_heads 1 and no int8 form (a scale a row of "
                f"kv_heads x head_dim is not a scale a latent row)")
        if self.latent and group.v_head_dim is not None:
            raise ValueError(
                f"cache group {group.name!r}: a group without a V has no "
                f"v_head_dim (its value is value_dim columns of its row)")
        self.v_head_dim = group.v_head_dim or group.head_dim
        if isinstance(self.k_pages, QuantizedKV) and (
                group.sink or self.v_head_dim != group.head_dim):
            raise NoInt8Form(
                f"cache group {group.name!r}: no int8 form of a group with "
                f"a softmax sink or with values of another width than its "
                f"keys ({group.head_dim} / {self.v_head_dim}): no attention "
                f"path serves one quantized")
        self.v_pages = None if self.latent else kv_zeros(
            (group.layers, num_pages, page_size, group.kv_heads,
             self.v_head_dim), kv_dtype)
        stores = [self.k_pages] + ([] if self.latent else [self.v_pages])
        # what ONE page stores, K and V each at its own width
        self.k_page_bytes = kv_nbytes(self.k_pages) // num_pages
        self.v_page_bytes = 0 if self.latent \
            else kv_nbytes(self.v_pages) // num_pages
        self.page_bytes = self.k_page_bytes + self.v_page_bytes
        self.scale_bytes = sum(map(kv_scale_nbytes, stores)) // num_pages
        self.free: List[int] = list(range(num_pages - 1, 0, -1))
        self.tables = np.zeros((max_seqs, pages_per_seq), np.int32)
        self.held = np.zeros((max_seqs,), np.int64)
        self.promised = np.zeros((max_seqs,), np.int64)
        # first table column a slot may still hold a page in: everything
        # before it was released behind the window
        self.first = np.zeros((max_seqs,), np.int64)
        self.n_released = 0

    @property
    def in_use(self) -> int:
        return self.num_pages - 1 - len(self.free)

    def status(self) -> dict:
        return {"name": self.name, "layers": self.group.layers,
                "window": self.window, "value_dim": self.group.value_dim,
                "kv_heads": self.group.kv_heads,
                "head_dim": self.group.head_dim,
                "v_head_dim": None if self.latent else self.v_head_dim,
                "sink": self.group.sink,
                "row_bytes": self.page_bytes // self.page_size,
                "k_row_bytes": self.k_page_bytes // self.page_size,
                "v_row_bytes": self.v_page_bytes // self.page_size,
                "page_bytes": self.page_bytes,
                "pages": self.num_pages, "in_use": self.in_use,
                "ring_pages": self.ring, "released": self.n_released}


class PagePool:
    """The groups of one engine. ``k_pages`` / ``v_pages`` / the device
    tables keep the FORM of the model's spec: one array for a bare triple
    (what such a model's forward reads), a tuple with one entry a group
    for a list."""

    def __init__(self, spec, num_pages: int, page_size: int, max_seqs: int,
                 pages_per_seq: int, kv_dtype, prefill_chunk: int):
        groups = cache_groups(spec)
        self.bare = is_bare(spec)
        self.page_size, self.pages_per_seq = page_size, pages_per_seq
        self.groups = [GroupPool(g, num_pages, page_size, max_seqs,
                                 pages_per_seq, kv_dtype, prefill_chunk)
                       for g in groups]
        self.windowed = any(g.window is not None for g in self.groups)
        self.latent = any(g.latent for g in self.groups)
        # the engine's prefix cache, over the pages of the one group of a
        # model without a window; None otherwise
        self.prefix_cache = None

    # -- the arrays, in the form of the spec ------------------------------
    def _form(self, per_group: Sequence[Any]):
        return per_group[0] if self.bare else tuple(per_group)

    def _set(self, attr: str, value) -> None:
        for g, v in zip(self.groups, [value] if self.bare else value):
            setattr(g, attr, v)

    @property
    def k_pages(self):
        return self._form([g.k_pages for g in self.groups])

    @k_pages.setter
    def k_pages(self, value) -> None:
        self._set("k_pages", value)

    @property
    def v_pages(self):
        return self._form([g.v_pages for g in self.groups])

    @v_pages.setter
    def v_pages(self, value) -> None:
        self._set("v_pages", value)

    def device_tables(self):
        """Every slot's block table, a group: ``[slots, pages_per_seq]``.
        A COPY of the host table: on the CPU ``jnp.asarray`` may alias the
        numpy buffer, and the allocator goes on writing it (a window
        group zeroes entries) while the dispatch that took it is still
        queued."""
        return self._form([jnp.asarray(g.tables.copy())
                           for g in self.groups])

    def host_tables(self) -> List[np.ndarray]:
        """The host block tables themselves, a group, in the order of
        ``groups``: for a caller that copies them into a buffer of its own
        before it sends anything (``StagedLayout.pack``). The allocator goes
        on writing them: never hand one to ``jnp.asarray`` as it is."""
        return [g.tables for g in self.groups]

    def row_tables(self, slots: np.ndarray):
        """The block table of each ROW's sequence, a group: ``slots``
        [...] holds a row's slot, -1 for a padded row (all zeros: the
        scratch page)."""
        pad = (slots < 0)[..., None]
        return self._form([
            jnp.asarray(np.where(pad, 0, g.tables[np.maximum(slots, 0)]))
            for g in self.groups])

    @property
    def page_bytes(self) -> int:
        """Bytes of one page of every group: what a token position costs
        a sequence that holds it in all of them."""
        return sum(g.page_bytes for g in self.groups)

    # -- the allocator, once, over groups ----------------------------------
    def _alloc(self, g: GroupPool) -> Optional[int]:
        if g.free:
            return g.free.pop()
        cache = self.prefix_cache
        if cache is not None and cache.evictable_count:
            # LRU eviction over refcount-zero cached pages; pages mapped
            # by a live sequence (ref > 0) are never candidates
            return cache.evict_one()
        return None

    def alloc(self) -> Optional[int]:
        """A page of the first group (the prefix cache's and the page
        migration's, which exist for a pool of one group only)."""
        return self._alloc(self.groups[0])

    def _avail(self, g: GroupPool) -> int:
        n = len(g.free) - int(np.maximum(g.promised - g.held, 0).sum())
        if self.prefix_cache is not None:
            n += self.prefix_cache.evictable_count
        return n

    def avail(self) -> int:
        """Pages the allocator could produce right now for a new sequence:
        the scarcest group's free pages (+ evictable refcount-zero cache
        residents) less what admitted sequences were promised and have not
        taken yet."""
        return min(self._avail(g) for g in self.groups)

    def _need(self, g: GroupPool, n_prompt: int, n_total: int) -> int:
        """Pages admission sets aside for a sequence. A pool without a
        window reserves the prompt's (as ever: decode grows on demand). A
        pool with one reserves what the sequence can hold at its longest,
        so nothing it admits is truncated: the ring for a window group,
        ``ceil(n_total / page)`` otherwise."""
        ps = self.page_size
        if not self.windowed:
            return -(-n_prompt // ps)
        pages = -(-n_total // ps)
        return pages if g.ring is None else min(pages, g.ring)

    def fits(self, n_prompt: int, n_total: int) -> bool:
        """Could a pool this size, empty, hold the sequence at all?"""
        return all(self._need(g, n_prompt, n_total)
                   <= min(g.num_pages - 1, self.pages_per_seq)
                   for g in self.groups)

    def admission(self, n_prompt: int, n_total: int, n_matched: int = 0,
                  n_matched_evictable: int = 0) -> str:
        """``"ok"``, ``"wait"`` (pages other sequences hold will free) or
        ``"never"`` (:meth:`fits` says no). ``n_matched`` pages come from
        the prefix cache, ``n_matched_evictable`` of them counted in
        :meth:`avail` too."""
        if not self.fits(n_prompt, n_total):
            return "never"
        if any(self._need(g, n_prompt, n_total) - n_matched
               > self._avail(g) - n_matched_evictable for g in self.groups):
            return "wait"
        return "ok"

    def admit(self, slot: int, n_prompt: int, n_total: int,
              matched: Sequence[int] = ()) -> None:
        """Give ``slot`` its pages of admission (:meth:`admission` said
        ``"ok"``): the prefix cache's ``matched`` pages mapped read-only,
        the rest of the prompt's pages allocated now in a group without a
        window, nothing yet in one with (its pages come a chunk at a time
        and go again behind the window)."""
        for g in self.groups:
            if self.windowed:
                g.promised[slot] = self._need(g, n_prompt, n_total)
            if g.ring is not None:
                continue
            for idx, page in enumerate(matched):
                self._map(g, slot, idx, page)
            for idx in range(len(matched), -(-n_prompt // self.page_size)):
                self._map(g, slot, idx, self._alloc(g))

    @staticmethod
    def _map(g: GroupPool, slot: int, idx: int, page: int) -> None:
        g.tables[slot, idx] = page
        g.held[slot] += 1

    def ensure(self, slot: int, pos: int,
               log: Optional[List[tuple]] = None) -> bool:
        """A page for token position ``pos`` in every group, allocated on
        demand; False: some group is exhausted. ``log`` collects
        ``(group, column)`` of what was newly allocated (:meth:`unmap`)."""
        idx = pos // self.page_size
        if idx >= self.pages_per_seq:
            return False
        for g in self.groups:
            if g.tables[slot, idx] == 0:
                page = self._alloc(g)
                if page is None:
                    return False
                self._map(g, slot, idx, page)
                if log is not None:
                    log.append((g, idx))
        return True

    def ensure_range(self, slot: int, first: int, count: int) -> None:
        """Pages for ``count`` positions from ``first``: a chunk of prompt
        rows. Admission set them aside, so none can be missing."""
        ps = self.page_size
        for idx in range(first // ps, (first + count - 1) // ps + 1):
            if not self.ensure(slot, idx * ps):
                raise RuntimeError(
                    f"slot {slot}: no page for prompt position {idx * ps} "
                    f"although admission reserved it")

    def unmap(self, g: GroupPool, slot: int, idx: int) -> None:
        """Take back a page :meth:`ensure` allocated and nothing wrote."""
        g.free.append(int(g.tables[slot, idx]))
        g.tables[slot, idx] = 0
        g.held[slot] -= 1

    def release_behind(self, slot: int, next_position: int) -> int:
        """After a dispatch wrote ``slot`` up to ``next_position - 1``: a
        window group frees the pages no later row can attend, those that
        end at or before ``next_position - window``. Returns how many."""
        total = 0
        for g in self.groups:
            if g.window is None:
                continue
            live_from = max(0, next_position - g.window + 1) \
                // self.page_size
            n = 0
            for idx in range(int(g.first[slot]), live_from):
                page = int(g.tables[slot, idx])
                if page > 0:
                    g.free.append(page)
                    g.tables[slot, idx] = 0
                    n += 1
            g.first[slot] = max(g.first[slot], live_from)
            g.held[slot] -= n
            g.n_released += n
            total += n
        return total

    def free_slot(self, slot: int) -> None:
        cache = self.prefix_cache
        for g in self.groups:
            for idx in np.flatnonzero(g.tables[slot]):
                page = int(g.tables[slot, idx])
                if cache is not None and cache.is_shared(page):
                    # shared page: drop this sequence's reference; at
                    # zero it stays CACHED (evictable): its KV is the
                    # whole point of the prefix cache
                    cache.release(page)
                else:
                    g.free.append(page)
            g.tables[slot] = 0
            g.held[slot] = g.promised[slot] = g.first[slot] = 0

    # -- gauges -----------------------------------------------------------
    def utilization(self) -> float:
        usable = sum(g.num_pages - 1 for g in self.groups)
        return sum(g.in_use for g in self.groups) / usable

    def pages_touched(self, calls) -> dict:
        """What the attention calls of one dispatch read and keep live, a
        group: ``{name: {"read", "live"}}``. Each of ``calls`` is ``(rows,
        padded_rows, impl)`` or ``(rows, padded_rows, impl, chunk)``:
        ``rows`` the ``(sequence, limit)`` pairs of the call's ONE-TOKEN
        rows (limit > 0: the row at position ``limit - 1``), ``chunk`` its
        packed prompt rows (:class:`ChunkRows`; None: it has none) and
        ``padded_rows`` the rows the programs carry, of both kinds. READ:
        the kernel a one-token row's pages from its window's first to its
        limit's, and a TILE of prompt rows its pages once (the kernel's own
        arithmetic, ``ops/paged_attention.py chunk_tiles``; an int8 pool's
        prompt rows a row each, as its kernel walks them); the gathered
        path every table entry of every row. LIVE: the distinct pages
        those rows can attend, a sequence's counted once."""
        ps = self.page_size
        calls = [(list(c[0]), c[1], c[2], c[3] if len(c) > 3 else None)
                 for c in calls]
        out = {}
        for g in self.groups:
            tiled = not isinstance(g.k_pages, QuantizedKV)

            def pages_of(limit):
                first = 0 if g.window is None \
                    else max(0, int(limit) - g.window) // ps
                return first, -(-int(limit) // ps)

            read = 0
            span: Dict[Any, tuple] = {}
            for rows, padded_rows, impl, chunk in calls:
                prompt = [] if chunk is None else chunk.live_rows()
                for seq, limit in rows + prompt:
                    first, last = pages_of(limit)
                    lo, hi = span.get(seq, (first, last))
                    span[seq] = (min(lo, first), max(hi, last))
                if impl != "pallas":
                    read += padded_rows * self.pages_per_seq
                    continue
                if prompt and tiled:
                    read += self._tile_pages(g, chunk)
                    prompt = []
                for _, limit in rows + prompt:      # walked a row each
                    first, last = pages_of(limit)
                    read += last - first
            out[g.name] = {"read": read,
                           "live": sum(hi - lo for lo, hi in span.values())}
        return out

    def _tile_pages(self, g: GroupPool, chunk: ChunkRows) -> int:
        """Pages the kernel's query tiles fetch for ``chunk`` in group
        ``g``: each program's rows cut as the kernel's plan cuts them."""
        programs, n = chunk.limits.shape
        qb = chunk_tile_rows(n)
        pad = ((0, 0), (0, -n % qb))
        limits = np.pad(chunk.limits.astype(np.int64), pad).ravel()
        seqs = np.pad(chunk.seqs, pad, constant_values=-1).ravel()
        starts = np.zeros_like(limits) if g.window is None \
            else np.maximum(limits - g.window, 0)
        new_seq = np.concatenate([[True], seqs[1:] != seqs[:-1]])
        head, _, first, last = chunk_tiles(new_seq, limits, starts,
                                           self.page_size, qb, xp=np)
        return int((last - first)[head].sum())
