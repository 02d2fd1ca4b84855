"""inference/page_pool.py: the allocator written once, over cache groups. A
bare triple is the list of one; a group with a window holds a ring of pages
a slot and frees what lies behind the window; every page comes back."""
import numpy as np
import pytest

from paddle_tpu.inference.page_pool import (CacheGroup, PagePool,
                                            cache_groups)
from paddle_tpu.inference.prefix_cache import PrefixCache

PS, SLOTS, PAGES_PER_SEQ, CHUNK, WINDOW = 4, 3, 32, 8, 12
RING = -(-(WINDOW + CHUNK) // PS) + 1                      # 6
TWO = [CacheGroup("full", 2, 2, 8), CacheGroup("window", 3, 2, 8, WINDOW)]


def pool_of(spec, num_pages=40):
    return PagePool(spec, num_pages, PS, SLOTS, PAGES_PER_SEQ, "f32", CHUNK)


def serve(pool, slot, n_prompt, n_new, on_tick=lambda: None):
    """What the engine does for one sequence: admission, the prompt a chunk
    at a time, then a token a tick; releases after every dispatch."""
    n_total = n_prompt + n_new
    assert pool.admission(n_prompt, n_total) == "ok"
    pool.admit(slot, n_prompt, n_total)
    for first in range(0, n_prompt, CHUNK):
        take = min(CHUNK, n_prompt - first)
        pool.ensure_range(slot, first, take)
        on_tick()
        pool.release_behind(slot, first + take)
    for pos in range(n_prompt, n_total):
        assert pool.ensure(slot, pos)
        on_tick()
        pool.release_behind(slot, pos + 1)


def test_a_bare_triple_is_the_list_of_one():
    assert cache_groups((4, 2, 8)) == [CacheGroup("kv", 4, 2, 8, None)]
    assert cache_groups(TWO) == TWO
    assert cache_groups([("a", 1, 2, 8, 5)]) == [CacheGroup("a", 1, 2, 8, 5)]
    pool = pool_of((4, 2, 8))
    (g,) = pool.groups
    assert not pool.windowed and g.ring is None and g.num_pages == 40
    # the arrays and tables keep the form of the spec: an array, not a tuple
    assert pool.k_pages.shape == (4, 40, PS, 2, 8)
    assert pool.device_tables().shape == (SLOTS, PAGES_PER_SEQ)
    assert pool.row_tables(np.asarray([0, -1, 2])).shape == (
        3, PAGES_PER_SEQ)
    assert pool.page_bytes == g.page_bytes == 2 * 4 * PS * 2 * 8 * 4
    pool.k_pages = pool.k_pages + 1
    assert float(g.k_pages[0, 0, 0, 0, 0]) == 1.0


def test_groups_have_their_own_arrays_tables_and_sizes():
    pool = pool_of(TWO)
    full, window = pool.groups
    assert pool.windowed and full.ring is None and window.ring == RING
    # a window group is given what it can ever hold, no more
    assert (full.num_pages, window.num_pages) == (40, SLOTS * RING + 1)
    assert [a.shape for a in pool.k_pages] == [
        (2, 40, PS, 2, 8), (3, SLOTS * RING + 1, PS, 2, 8)]
    assert window.page_bytes == full.page_bytes * 3 // 2
    tables = pool.device_tables()
    assert isinstance(tables, tuple) and len(tables) == 2
    new = tuple(a + 1 for a in pool.v_pages)
    pool.v_pages = new
    assert window.v_pages is new[1]


def test_one_lifetime_keeps_every_page_until_the_slot_is_freed():
    pool = pool_of((4, 2, 8))
    (g,) = pool.groups
    serve(pool, 1, n_prompt=21, n_new=30)
    assert int(g.held[1]) == -(-51 // PS) and g.n_released == 0
    assert pool.utilization() == pytest.approx(13 / 39)
    pool.free_slot(1)
    assert len(g.free) == 39 and not g.tables.any()


def test_a_window_group_holds_a_ring_and_frees_behind_the_window():
    pool = pool_of(TWO)
    full, window = pool.groups
    peak = []
    serve(pool, 0, n_prompt=50, n_new=40,
          on_tick=lambda: peak.append(int(window.held[0])))
    assert max(peak) <= RING
    assert int(full.held[0]) == -(-90 // PS)
    # what is left is what the next row (position 90) can attend
    live = np.flatnonzero(window.tables[0])
    assert live.min() == (90 - WINDOW + 1) // PS and live.max() == 89 // PS
    assert window.n_released == live.min() and full.n_released == 0
    assert int(window.held[0]) == len(live)
    pool.free_slot(0)
    for g in pool.groups:
        assert sorted(g.free) == list(range(1, g.num_pages))
        assert not g.tables.any() and not g.held.any()


def test_admission_reserves_the_longest_a_windowed_sequence_can_hold():
    """Every slot's ring is set aside when it is admitted, so a chunk's
    pages are always there; a sequence no pool this size holds is never
    admitted, one that must wait for pages is told to wait."""
    pool = pool_of(TWO, num_pages=30)         # full: 29 usable pages
    assert pool.admission(40, 200) == "never"          # 50 pages of full
    assert pool.admission(40, 100) == "ok"             # 25
    pool.admit(0, 40, 100)
    assert pool.avail() == 29 - 25
    assert pool.admission(8, 40) == "wait"             # 10 > 4 left
    assert pool.admission(8, 16) == "ok"
    pool.admit(1, 8, 16)
    # the promise holds although nothing of it is allocated yet
    window = pool.groups[1]
    assert int(window.held.sum()) == 0
    assert pool._avail(window) == SLOTS * RING - RING - 4
    pool.free_slot(0)
    pool.free_slot(1)
    assert pool.avail() == min(29, SLOTS * RING)


def test_ensure_logs_what_it_allocated_and_unmap_takes_it_back():
    pool = pool_of(TWO)
    pool.admit(2, 4, 40)
    log = []
    assert pool.ensure(2, 4, log) and pool.ensure(2, 5, log)
    assert [(g.name, idx) for g, idx in log] == [("full", 1), ("window", 1)]
    for g, idx in log:
        pool.unmap(g, 2, idx)
    assert [int(g.held[2]) for g in pool.groups] == [1, 0]
    assert not pool.ensure(2, PAGES_PER_SEQ * PS)      # past the table


def test_the_prefix_cache_serves_the_one_group_of_a_pool_without_a_window():
    pool = pool_of((2, 2, 8), num_pages=4)
    cache = pool.prefix_cache = PrefixCache(PS)
    pool.admit(0, 8, 8)
    cache.register(b"a" * 16, int(pool.groups[0].tables[0, 0]), [1, 2, 3, 4])
    pool.free_slot(0)          # the shared page stays cached, one is free
    (g,) = pool.groups
    assert len(g.free) == 2 and cache.evictable_count == 1
    assert pool.avail() == 3
    assert pool.alloc() and pool.alloc() and pool.alloc()   # the last evicts
    assert pool.alloc() is None and cache.evictable_count == 0


def test_pages_touched_by_group():
    """A decode row of a long sequence reads its whole context from the
    full group and its window from the window group; a chunk's rows each
    walk their own; the gathered path reads every table entry."""
    pool = pool_of(TWO)
    got = pool.pages_touched([([(0, 90)], SLOTS, "pallas")])
    first = (90 - WINDOW) // PS
    assert got == {"full": {"read": 23, "live": 23},
                   "window": {"read": 23 - first, "live": 23 - first}}
    chunk = [(1, p + 1) for p in range(16, 24)]
    got = pool.pages_touched([(iter(chunk), CHUNK, "pallas")])
    assert got["full"] == {"read": sum(-(-(p + 1) // PS)
                                       for p in range(16, 24)), "live": 6}
    assert got["window"]["live"] == 6 - (17 - WINDOW) // PS
    got = pool.pages_touched([(chunk, CHUNK, "xla")])
    assert got["full"]["read"] == got["window"]["read"] \
        == CHUNK * PAGES_PER_SEQ


# -- a latent group: a row a token, no V, no head axis ------------------------

LATENT = [CacheGroup("latent", 3, 1, 128, None, 96)]


def test_a_latent_group_keeps_one_array_and_no_v():
    pool = pool_of(LATENT)
    (g,) = pool.groups
    assert pool.latent and g.latent and not pool.windowed and not pool.bare
    # [layers, pages, page_size, row width]: no head axis, and no V at all
    assert g.k_pages.shape == (3, 40, PS, 128) and g.v_pages is None
    assert pool.k_pages[0] is g.k_pages and pool.v_pages == (None,)
    # what a page costs is what is stored: one row a token a layer
    assert g.page_bytes == 3 * PS * 128 * 4 == pool.page_bytes
    assert g.status()["row_bytes"] == 3 * 128 * 4
    assert g.status()["value_dim"] == 96
    assert not pool_of(TWO).latent and pool_of(TWO).groups[0].status()[
        "value_dim"] is None
    # the programs hand the arrays back in the form they took them in
    pool.k_pages = (g.k_pages + 1,)
    pool.v_pages = (None,)
    assert float(g.k_pages[0, 0, 0, 0]) == 1.0 and g.v_pages is None
    # the allocator does not know the difference
    serve(pool, 0, 19, 9)
    assert g.held[0] == -(-28 // PS)
    pool.free_slot(0)
    assert len(g.free) == g.num_pages - 1


@pytest.mark.parametrize("spec,kv_dtype", [
    (LATENT, "int8"), ([CacheGroup("latent", 3, 2, 128, None, 96)], "f32")],
    ids=["int8", "two-kv-heads"])
def test_a_latent_group_has_one_head_and_no_int8_form(spec, kv_dtype):
    with pytest.raises(ValueError, match="without a V"):
        PagePool(spec, 40, PS, SLOTS, PAGES_PER_SEQ, kv_dtype, CHUNK)


def test_pages_touched_by_a_latent_group_are_the_pages_of_one_array():
    """The row walk reads a row's live pages, a tile of prompt rows its
    sequence's pages once: the same counts as a K/V group's (a page is a
    page), priced at the latent page's bytes by the readers."""
    from paddle_tpu.inference.page_pool import ChunkRows
    pool, kv = pool_of(LATENT), pool_of([CacheGroup("kv", 3, 2, 8)])
    rows = [(0, 9), (1, 30)]
    chunk = ChunkRows(np.asarray([[2] * 8]),
                      np.asarray([np.arange(20, 28) + 1]))
    got = pool.pages_touched([(rows, 10, "pallas", chunk)])["latent"]
    assert got == kv.pages_touched([(rows, 10, "pallas", chunk)])["kv"]
    assert got == {"read": 3 + 8 + 7, "live": 3 + 8 + 7}


# -- values of another width than the keys; a sink; a window under the chunk --

WIDE = [CacheGroup("full", 2, 1, 24, None, None, 16),
        CacheGroup("window", 3, 2, 24, 4, None, 16, True)]


def test_a_group_with_v_head_dim_keeps_a_v_array_of_its_own_width():
    pool = pool_of(WIDE)
    full, window = pool.groups
    assert full.k_pages.shape == (2, 40, PS, 1, 24)
    assert full.v_pages.shape == (2, 40, PS, 1, 16)
    assert window.k_pages.shape[-2:] == (2, 24) \
        and window.v_pages.shape[-2:] == (2, 16)
    # the bytes are what is stored, K and V each at its own width
    assert (full.k_page_bytes, full.v_page_bytes) == (
        2 * PS * 24 * 4, 2 * PS * 16 * 4)
    assert full.page_bytes == 2 * PS * (24 + 16) * 4
    assert window.page_bytes == 3 * PS * 2 * (24 + 16) * 4
    assert pool.page_bytes == full.page_bytes + window.page_bytes
    st = window.status()
    assert (st["head_dim"], st["v_head_dim"], st["sink"], st["kv_heads"]) \
        == (24, 16, True, 2)
    assert (st["k_row_bytes"], st["v_row_bytes"], st["row_bytes"]) == (
        3 * 2 * 24 * 4, 3 * 2 * 16 * 4, 3 * 2 * 40 * 4)
    assert full.status()["sink"] is False
    # a group that names no v_head_dim: V as wide as K, as ever
    same = pool_of(TWO).groups[0]
    assert same.v_pages.shape == same.k_pages.shape
    assert same.status()["v_head_dim"] == 8 \
        and same.k_page_bytes == same.v_page_bytes
    # the arrays keep the form of the spec, V beside K
    assert [v.shape[-1] for v in pool.v_pages] == [16, 16]


@pytest.mark.parametrize("group", [
    CacheGroup("kv", 2, 2, 24, None, None, 16),
    CacheGroup("kv", 2, 2, 24, None, None, None, True)],
    ids=["unequal-widths", "sink"])
def test_no_int8_form_of_unequal_widths_or_of_a_sink(group):
    with pytest.raises(ValueError, match="no int8 form of a group with"):
        PagePool([group], 40, PS, SLOTS, PAGES_PER_SEQ, "int8", CHUNK)
    PagePool([group], 40, PS, SLOTS, PAGES_PER_SEQ, "f32", CHUNK)
    with pytest.raises(ValueError, match="has no v_head_dim"):
        PagePool([CacheGroup("latent", 1, 1, 128, None, 96, 64)], 40, PS,
                 SLOTS, PAGES_PER_SEQ, "f32", CHUNK)


def test_a_ring_under_a_chunk_longer_than_the_window():
    """Window 4 under chunks of 8 (the cell: 128 under 256): the ring is
    the window, the chunk in front of it and a page of misalignment; a slot
    never holds more, whatever the sequence's length, a chunk's pages are
    all there while its program runs, and a page a slot is freed every
    page of tokens."""
    pool = pool_of(WIDE)
    window = pool.groups[1]
    assert window.ring == -(-(4 + CHUNK) // PS) + 1 == 4
    assert window.num_pages == SLOTS * 4 + 1
    peak, held_at_tick = [0], []

    def on_tick():
        peak[0] = max(peak[0], int(window.held[0]))
        held_at_tick.append(int(window.held[0]))

    serve(pool, 0, 37, 30, on_tick)
    assert peak[0] <= window.ring
    # a chunk of 8 holds its own two pages and the window's page before
    assert max(held_at_tick[:4]) == 3
    # decode: the window's one or two pages, and a page goes every 4 tokens
    assert set(held_at_tick[6:]) <= {1, 2}
    assert window.n_released == (37 + 30 - 4 + 1) // PS
    assert pool.groups[0].held[0] == -(-(37 + 30) // PS)
    pool.free_slot(0)
    assert len(window.free) == window.num_pages - 1


def test_pages_touched_by_a_chunk_twice_the_window():
    """A chunk of 8 rows over window 4 through the kernel: one tile walks
    the pages from its first row's window to its last row's limit once."""
    from paddle_tpu.inference.page_pool import ChunkRows
    pool = pool_of(WIDE)
    chunk = ChunkRows(np.zeros((1, 8), np.int32),
                      np.arange(17, 25, dtype=np.int32)[None])
    got = pool.pages_touched([([], 8, "pallas", chunk)])
    # limits 17..24: the full group reads pages 0-5 once; the window group
    # from position 13 (page 3) to position 23 (page 5)
    assert got["full"] == {"read": 6, "live": 6}
    assert got["window"] == {"read": 3, "live": 3}


# -- the head axis is the spec's: a model that wants whole tiles names them ----

@pytest.mark.parametrize("kv_heads", [1, 2, 4, 8, 16, 6, 12, 25, 30, 32])
def test_a_group_stores_the_heads_its_spec_names(kv_heads):
    """The pool rounds nothing: 12 heads (GPT-2) are stored as 12, and the
    32 that ``models/olmo_hybrid.py`` names for its thirty as 32;
    ``status()``, ``page_bytes`` and the arrays agree."""
    pool = pool_of([CacheGroup("full", 2, kv_heads, 8)])
    (g,) = pool.groups
    assert g.k_pages.shape == g.v_pages.shape == (2, 40, PS, kv_heads, 8)
    assert g.page_bytes == 2 * 2 * PS * kv_heads * 8 * 4
    status = g.status()
    assert status["kv_heads"] == kv_heads
    assert status["row_bytes"] == 2 * 2 * kv_heads * 8 * 4
    assert status["k_row_bytes"] == status["v_row_bytes"] \
        == 2 * kv_heads * 8 * 4


@pytest.mark.parametrize("kv_heads", [6, 30])
def test_pages_touched_do_not_depend_on_the_heads_a_page_stores(kv_heads):
    """The counter reckons pages: the group a model of 6 or 30 K/V heads
    names (8, 32 stored) reads and keeps live what a group of 2 does, row
    walk, tiles and gathered path."""
    from paddle_tpu.inference.page_pool import ChunkRows
    from paddle_tpu.models.olmo_hybrid import OlmoHybridConfig
    stored = OlmoHybridConfig(
        hidden_size=kv_heads * 8, num_attention_heads=kv_heads,
        num_key_value_heads=kv_heads, num_layers=4).stored_kv_heads
    assert stored == (32 if kv_heads == 30 else 8)
    odd, two = (pool_of([CacheGroup("full", 2, n, 8)])
                for n in (stored, 2))
    for p in (odd, two):
        serve(p, 0, 21, 3)
    chunk = ChunkRows(np.asarray([[1] * 8]),
                      np.asarray([[p + 1 for p in range(16, 24)]]))
    for call in (([(0, 24)], SLOTS, "pallas"),
                 ([], 8, "pallas", chunk),
                 ([(0, 24)], SLOTS, "xla")):
        assert odd.pages_touched([call])["full"] \
            == two.pages_touched([call])["full"]
    assert odd.groups[0].page_bytes == two.groups[0].page_bytes \
        * stored // 2
