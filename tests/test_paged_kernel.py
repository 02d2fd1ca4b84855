"""The paged-attention kernel over the STACKED pool, in interpret mode,
against ``ragged_paged_attention_reference``: the layer index, ragged
limits in one call, chunk rows sharing a table beside decode rows, GQA,
int8 scales, and inside a ``lax.scan``. ``tests/test_chip_compile.py``
compiles the same signature for a described v5e."""

import importlib

import numpy as np
import pytest
import jax
import jax.numpy as jnp

# the module, not the op of the same name that ``paddle_tpu.ops`` exports
pa = importlib.import_module("paddle_tpu.ops.paged_attention")
QuantizedKV, quantize_kv = pa.QuantizedKV, pa.quantize_kv
paged_attention_kernel = pa.paged_attention_kernel
ragged_paged_attention = pa.ragged_paged_attention
ragged_paged_attention_reference = pa.ragged_paged_attention_reference

LAYERS, PAGES, PS, D = 3, 40, 4, 16
PAGES_PER_SEQ = 8                       # a table of 32 tokens
MAX_LEN = PAGES_PER_SEQ * PS


@pytest.fixture(params=[2, None], ids=["blocks-of-2-pages", "one-block"])
def block(request, monkeypatch):
    """Pages the kernel moves a step: two (a table is then four blocks, so
    block boundaries and the prefetch across rows are exercised), or what
    the shapes give (the whole table of these tiny pages)."""
    if request.param is not None:
        monkeypatch.setattr(pa, "_pages_per_block",
                            lambda *a: request.param)
    return request.param or PAGES_PER_SEQ


def pool(seed, kv_heads, dtype=jnp.float32, layers=LAYERS):
    k, v = jax.random.normal(jax.random.PRNGKey(seed),
                             (2, layers, PAGES, PS, kv_heads, D))
    return k.astype(dtype), v.astype(dtype)


def tables_for(rows, seed=0):
    """Distinct pages for every row, none of them page 0."""
    perm = np.random.RandomState(seed).permutation(np.arange(1, PAGES))
    return jnp.asarray(np.resize(perm, (rows, PAGES_PER_SEQ)), jnp.int32)


def queries(rows, heads, seed=1):
    return jax.random.normal(jax.random.PRNGKey(seed), (rows, heads, D))


def reference(q, k, v, tables, lens, layer):
    return np.asarray(ragged_paged_attention_reference(
        q, pa.kv_layer(k, layer), pa.kv_layer(v, layer), tables, lens))


@pytest.mark.parametrize("layer", [0, 1, LAYERS - 1],
                         ids=["first", "middle", "last"])
def test_kernel_attends_the_named_layer_of_the_stacked_pool(block, layer):
    k, v = pool(0, kv_heads=2)
    q = queries(5, 2)
    tables = tables_for(5)
    lens = jnp.asarray([9, MAX_LEN, 1, 0, 17], jnp.int32)
    got = np.asarray(paged_attention_kernel(q, k, v, tables, lens,
                                            layer=layer))
    np.testing.assert_allclose(got, reference(q, k, v, tables, lens, layer),
                               atol=2e-5, rtol=2e-5)
    # a traced layer index reads the same pages
    traced = jax.jit(lambda i: paged_attention_kernel(
        q, k, v, tables, lens, layer=i))(jnp.int32(layer))
    np.testing.assert_array_equal(np.asarray(traced), got)
    if layer:   # and they are not another layer's
        other = reference(q, k, v, tables, lens, layer - 1)
        assert np.abs(got - other).max() > 1e-2


def test_ragged_limits_in_one_call(block):
    """Limit 0, 1, exactly a page, one over a page boundary, a block
    boundary and one over it, ``max_len``; rows with nothing to attend
    first, in the middle and last (the prefetch across rows skips them)."""
    span = block * PS
    lens = [0, 1, PS, PS + 1, 0, 0, min(span, MAX_LEN),
            min(span + 1, MAX_LEN), MAX_LEN, 3, 0]
    k, v = pool(2, kv_heads=2)
    q = queries(len(lens), 2)
    tables = tables_for(len(lens), seed=2)
    lens = jnp.asarray(lens, jnp.int32)
    got = np.asarray(paged_attention_kernel(q, k, v, tables, lens, layer=1))
    np.testing.assert_allclose(got, reference(q, k, v, tables, lens, 1),
                               atol=2e-5, rtol=2e-5)
    assert not got[np.asarray(lens) == 0].any(), "limit 0 is a zero row"
    # nothing live at all: every row zero, no page touched
    none = np.asarray(paged_attention_kernel(
        q, k, v, tables, jnp.zeros_like(lens), layer=1))
    assert not none.any()


def test_chunk_rows_share_a_table_beside_decode_rows(block):
    """A mixed tick's batch: six rows of one prompt, each its sequence's
    table and a limit one longer than the row before (causal inside the
    chunk), three rows of another, then decode rows of their own."""
    k, v = pool(3, kv_heads=2)
    own = tables_for(6, seed=3)
    tables = jnp.concatenate([jnp.repeat(own[:1], 6, axis=0),
                              jnp.repeat(own[1:2], 3, axis=0), own[2:]])
    lens = jnp.asarray(list(range(7, 13)) + [1, 2, 3] + [20, 0, 5, MAX_LEN],
                       jnp.int32)
    q = queries(len(lens), 2, seed=4)
    got = np.asarray(ragged_paged_attention(q, k, v, tables, lens,
                                            impl="pallas", layer=2))
    np.testing.assert_allclose(got, reference(q, k, v, tables, lens, 2),
                               atol=2e-5, rtol=2e-5)
    # the entry point's gathered path takes the same layer of the same pool
    np.testing.assert_allclose(
        np.asarray(ragged_paged_attention(q, k, v, tables, lens, layer=2)),
        got, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("heads,kv_heads,dtype", [
    (4, 2, jnp.float32), (8, 2, jnp.bfloat16), (4, 4, jnp.bfloat16)],
    ids=["gqa4-2-f32", "gqa8-2-bf16", "mha4-bf16"])
def test_gqa_and_bf16_pages(block, heads, kv_heads, dtype):
    k, v = pool(5, kv_heads, dtype)
    q = queries(4, heads, seed=6)
    tables = tables_for(4, seed=5)
    lens = jnp.asarray([11, 0, MAX_LEN, 6], jnp.int32)
    got = np.asarray(paged_attention_kernel(q, k, v, tables, lens, layer=1))
    tol = 2e-5 if dtype == jnp.float32 else 1e-2
    np.testing.assert_allclose(got, reference(q, k, v, tables, lens, 1),
                               atol=tol, rtol=tol)


def test_int8_pages_with_scales_beside_them(block):
    """The stacked int8 pool: pages cross as int8, the scale rows of the
    call's layer dequantize them in the kernel."""
    kf, vf = pool(7, kv_heads=2)
    kq, ks = quantize_kv(kf)
    vq, vs = quantize_kv(vf)
    q = queries(5, 4, seed=8)
    tables = tables_for(5, seed=7)
    lens = jnp.asarray([13, 1, 0, MAX_LEN, PS], jnp.int32)
    got = np.asarray(ragged_paged_attention(
        q, QuantizedKV(kq, ks), QuantizedKV(vq, vs), tables, lens,
        impl="pallas", layer=2))
    want = np.asarray(ragged_paged_attention_reference(
        q, QuantizedKV(kq[2], ks[2]), QuantizedKV(vq[2], vs[2]), tables,
        lens))
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)
    # and within the quantization tolerance of the float pool
    exact = reference(q, kf, vf, tables, lens, 2)
    assert np.abs(got - exact).max() < 0.05


def test_inside_a_scan_over_layers_and_ticks(block):
    """Callable from a ``lax.scan`` body with the pool in the carry and the
    layer a traced value: every layer's output equals its own call."""
    k, v = pool(9, kv_heads=2)
    q = queries(3, 2, seed=10)
    tables = tables_for(3, seed=9)
    lens = jnp.asarray([10, 0, 25], jnp.int32)

    def tick(carry, layer):
        kk, vv = carry
        return carry, paged_attention_kernel(q, kk, vv, tables, lens,
                                             layer=layer)

    _, outs = jax.jit(lambda k, v: jax.lax.scan(
        tick, (k, v), jnp.arange(LAYERS)))(k, v)
    for layer in range(LAYERS):
        np.testing.assert_allclose(
            np.asarray(outs[layer]), reference(q, k, v, tables, lens, layer),
            atol=2e-5, rtol=2e-5)


def test_a_single_layers_pages_are_the_one_layer_view():
    """Four-dimensional pages (the pre-stacked signature) still attend."""
    k, v = pool(11, kv_heads=2, layers=1)
    q = queries(3, 2, seed=12)
    tables = tables_for(3, seed=11)
    lens = jnp.asarray([5, 0, 30], jnp.int32)
    got = np.asarray(paged_attention_kernel(q, k[0], v[0], tables, lens))
    np.testing.assert_allclose(got, reference(q, k, v, tables, lens, 0),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("dtype,kv_heads,want", [
    (jnp.bfloat16, 16, 16), (jnp.bfloat16, 4, 16), (jnp.int8, 16, 16),
    (jnp.float32, 16, 8)], ids=["bf16-mha", "bf16-gqa4", "int8", "f32"])
def test_block_follows_the_shapes(dtype, kv_heads, want):
    """Pages a step at the 1.3B head shape (page 16, d 128): the four page
    buffers of the VMEM plan over a page's tiled size, never more than the
    table holds."""
    assert pa._pages_per_block(16, kv_heads, 128, dtype, 128) == want
    assert pa._pages_per_block(16, kv_heads, 128, dtype, 4) == 4


# -- a lower bound a row (``starts=``): a sliding window's rows ---------------

def dense_window(q, k, v, tables, lens, starts, layer):
    """The definition, a row at a time: softmax over positions ``starts[r]
    <= j < lens[r]`` of the row's own pages."""
    q, k, v = (np.asarray(a, np.float64) for a in (q, k, v))
    tables, out = np.asarray(tables), np.zeros(q.shape)
    group = q.shape[1] // k.shape[3]
    for r in range(q.shape[0]):
        lo, hi = int(starts[r]), int(lens[r])
        if hi == 0:
            continue
        kk = k[layer, tables[r]].reshape(-1, k.shape[3], D)[lo:hi]
        vv = v[layer, tables[r]].reshape(-1, k.shape[3], D)[lo:hi]
        for h in range(q.shape[1]):
            s = kk[:, h // group] @ q[r, h] / np.sqrt(D)
            p = np.exp(s - s.max())
            out[r, h] = (p / p.sum()) @ vv[:, h // group]
    return out


@pytest.mark.parametrize("heads,kv_heads", [(2, 2), (12, 2), (18, 2)],
                         ids=["mha", "6-a-kv-head", "9-a-kv-head"])
def test_a_lower_bound_a_row_in_all_three_paths(block, heads, kv_heads):
    """Windows that begin at a page's first token, inside a page, in the
    limit's own page and at 0; a window of one token; a row with nothing to
    attend. The kernel, the gathered path and the f32 reference agree with
    the definition."""
    k, v = pool(3, kv_heads)
    q = queries(8, heads)
    tables = tables_for(8, seed=5)
    lens = jnp.asarray([MAX_LEN, 30, 17, 9, 1, 0, 13, 24], jnp.int32)
    starts = jnp.asarray([MAX_LEN - 8, 11, 16, 0, 0, 0, 12, 3], jnp.int32)
    want = dense_window(q, k, v, tables, lens, starts, layer=1)
    for impl in ("pallas", "xla", "reference"):
        got = ragged_paged_attention(q, k, v, tables, lens, impl=impl,
                                     layer=1, starts=starts)
        np.testing.assert_allclose(np.asarray(got), want, atol=2e-5,
                                   rtol=2e-5, err_msg=impl)
    # a bound that cuts nothing is no bound; and the bound matters
    whole = ragged_paged_attention(q, k, v, tables, lens, impl="pallas",
                                   layer=1)
    np.testing.assert_allclose(
        np.asarray(ragged_paged_attention(
            q, k, v, tables, lens, impl="pallas", layer=1,
            starts=jnp.zeros_like(lens))), np.asarray(whole), atol=1e-6)
    assert np.abs(np.asarray(whole)[:3] - want[:3]).max() > 1e-2


def test_no_lower_bound_is_todays_program_bit_for_bit(block):
    """``starts=None`` lowers to the same kernel as before the argument
    existed: the same jaxpr, so the same program and the same bits."""
    k, v = pool(0, kv_heads=2)
    q = queries(5, 4)
    tables = tables_for(5)
    lens = jnp.asarray([9, MAX_LEN, 1, 0, 17], jnp.int32)
    with_none = jax.make_jaxpr(lambda *a: ragged_paged_attention(
        *a, impl="pallas", layer=2, starts=None))(q, k, v, tables, lens)
    without = jax.make_jaxpr(lambda *a: ragged_paged_attention(
        *a, impl="pallas", layer=2))(q, k, v, tables, lens)
    import re

    def text(jaxpr):                 # without the addresses of functions
        return re.sub(r"0x[0-9a-f]+", "0x", str(jaxpr))

    assert text(with_none) == text(without)
    bounded = jax.make_jaxpr(lambda *a: ragged_paged_attention(
        *a, impl="pallas", layer=2, starts=jnp.zeros_like(lens)))(
        q, k, v, tables, lens)
    assert text(bounded) != text(without)
    for impl in ("pallas", "xla"):
        np.testing.assert_array_equal(
            np.asarray(ragged_paged_attention(q, k, v, tables, lens,
                                              impl=impl, layer=2,
                                              starts=None)),
            np.asarray(ragged_paged_attention(q, k, v, tables, lens,
                                              impl=impl, layer=2)))


def test_a_windowed_row_fetches_no_page_before_its_window(block):
    """Pages wholly before ``starts`` are never read: their table entries
    point at pages full of NaN, and the result is finite and right."""
    k, v = pool(4, kv_heads=2)
    q = queries(3, 12)
    tables = np.asarray(tables_for(3, seed=7)).copy()
    lens = jnp.asarray([MAX_LEN, 22, 15], jnp.int32)
    starts = jnp.asarray([MAX_LEN - 6, 9, 12], jnp.int32)
    want = dense_window(q, k, v, tables, lens, starts, layer=0)
    poisoned = PAGES - 1
    k = k.at[:, poisoned].set(jnp.nan)
    v = v.at[:, poisoned].set(jnp.nan)
    for r in range(3):
        tables[r, :int(starts[r]) // PS] = poisoned
    got = ragged_paged_attention(q, k, v, jnp.asarray(tables), lens,
                                 impl="pallas", layer=0, starts=starts)
    np.testing.assert_allclose(np.asarray(got), want, atol=2e-5, rtol=2e-5)


@pytest.fixture(params=[None, 3], ids=["fold-a-block", "fold-3-pages"])
def mxu_fold(request, monkeypatch, block):
    """Pages of a fold on the MXU: what the shapes give (these tiny blocks
    whole), or three, so that the one-block table is two folds and its last
    one is a page short. ``mxu_fold(heads, kv_heads)`` sets the scores'
    budget for such a call and returns the tokens of a fold."""
    if request.param is not None and block <= request.param:
        pytest.skip("a block of two pages is one fold either way")

    def tokens_of_a_fold(heads, kv_heads):
        if request.param is not None:
            monkeypatch.setattr(pa, "_MXU_SCORE_BYTES",
                                request.param * 4 * heads * kv_heads * PS)
        return (request.param or block) * PS
    yield tokens_of_a_fold
    # each case is an interpreted kernel of its own shapes that no other
    # test runs again: let the executables go (with them all kept, a process
    # that runs this file whole dies in XLA:CPU's compiler ~60 tests later)
    jax.clear_caches()


@pytest.mark.parametrize("bound", [False, True], ids=["whole", "windowed"])
@pytest.mark.parametrize("dtype,tol", [(jnp.bfloat16, 2e-2),
                                       (jnp.float32, 2e-5)],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("kv_heads", [2, 8])
@pytest.mark.parametrize("group", [6, 9], ids=["6-a-kv-head", "9-a-kv-head"])
def test_many_heads_a_kv_head_fold_on_the_mxu_in_the_pools_type(
        block, mxu_fold, group, kv_heads, dtype, tol, bound):
    """Over ``_VPU_GROUP_ROWS`` query heads a K/V head the fold is two
    products in the pool's type: with bf16 pages the queries and
    the probabilities are rounded to bf16 (float32 sums), so the result lies
    within bf16's rounding of the float32 reference over the same pages; a
    float32 pool is exact. Rows of no token, one, one short of a page,
    exactly a fold, one over, a window that begins inside a page, and the
    whole table; with a lower bound too. Few heads a K/V head stay on the
    VPU path."""
    assert group > pa._VPU_GROUP_ROWS >= 4
    fold = mxu_fold(group * kv_heads, kv_heads)
    k, v = pool(7, kv_heads, dtype)
    lens = jnp.asarray([0, 1, PS - 1, fold, min(fold + 1, MAX_LEN), 22,
                        MAX_LEN], jnp.int32)
    starts = jnp.asarray([0, 0, 1, 0, 2, 9, MAX_LEN - 9], jnp.int32) \
        if bound else None
    rows = lens.shape[0]
    q = queries(rows, group * kv_heads, seed=8).astype(dtype)
    tables = tables_for(rows, seed=9)
    got = np.asarray(paged_attention_kernel(
        q, k, v, tables, lens, layer=1, starts=starts), np.float32)
    want = np.asarray(ragged_paged_attention_reference(
        q, pa.kv_layer(k, 1), pa.kv_layer(v, 1), tables, lens,
        starts=starts))
    np.testing.assert_allclose(got, want, atol=tol, rtol=tol)
    assert np.abs(got - want).max() < tol
    assert np.abs(got[0]).max() == 0.0          # nothing to attend: zeros


def test_a_fold_on_the_mxu_reads_nothing_its_row_did_not_fetch(block,
                                                               mxu_fold):
    """A fold takes whole groups of pages, so the last one of a row reaches
    past what the row's copies wrote: into the STALE TAIL of the buffer
    slot, here the pages of a row before it whose every value is NaN, and
    never into a page beyond the row's limit (those table entries name a
    page of NaN too). Neither changes a bit of the other rows' output."""
    kv_heads, group = 2, 6
    mxu_fold(group * kv_heads, kv_heads)
    k, v = pool(11, kv_heads)
    q = queries(6, group * kv_heads, seed=12)
    lens = np.asarray([MAX_LEN, 5, 1, MAX_LEN - 3, 2, 9], np.int32)
    # rows 0 and 3 fill both slots with NaN; every entry past a limit too
    healthy, poisoned = [1, 2, 4, 5], list(range(1, 17)) + [PAGES - 1]
    tables = np.full((6, PAGES_PER_SEQ), PAGES - 1, np.int32)
    tables[0], tables[3] = np.arange(1, 9), np.arange(9, 17)
    for r in healthy:
        live = -(-lens[r] // PS)
        tables[r, :live] = 17 + 3 * r + np.arange(live)
    want = reference(q, k, v, jnp.asarray(tables), jnp.asarray(lens), layer=2)
    clean = np.asarray(paged_attention_kernel(
        q, k, v, jnp.asarray(tables), jnp.asarray(lens), layer=2))
    k = k.at[:, np.asarray(poisoned)].set(jnp.nan)
    v = v.at[:, np.asarray(poisoned)].set(jnp.nan)
    got = np.asarray(paged_attention_kernel(
        q, k, v, jnp.asarray(tables), jnp.asarray(lens), layer=2))
    assert np.isnan(got[0]).all() and np.isnan(got[3]).all()
    np.testing.assert_array_equal(got[healthy], clean[healthy])
    np.testing.assert_allclose(got[healthy], want[healthy], atol=2e-5,
                               rtol=2e-5)


# -- query tiles (``n_chunk=``): a chunk of packed prompt rows ---------------

@pytest.fixture(params=[8, None], ids=["tiles-of-8-rows", "one-tile"])
def tile_rows(request, monkeypatch):
    """Rows of a query tile: eight (the 20-row chunk below is then three
    windows, one of them cut twice by a sequence boundary), or what the
    chunk gives (one window of 24)."""
    if request.param is not None:
        monkeypatch.setattr(pa, "_TILE_ROWS", request.param)
    return request.param


def packing(decode=(20, 0, 5, MAX_LEN)):
    """A mixed tick's rows. The chunk: eleven rows of sequence A from
    position 5 on (mid-prompt, not a page's first), ONE row of B, six rows
    of C from its first position, two padded rows; then ``decode`` rows of
    their own. With tiles of eight rows, A's run is cut by a window's edge
    and B and C begin inside a tile's width."""
    runs = [(6, 11), (9, 1), (1, 6), (0, 2)]       # (first limit, rows)
    own = np.asarray(tables_for(len(runs) + len(decode), seed=13))
    tables, lens = [], []
    for i, (first, n) in enumerate(runs):
        for j in range(n):
            tables.append(own[i] if first else np.zeros_like(own[i]))
            lens.append(first + j if first else 0)
    n_chunk = len(lens)
    for i, limit in enumerate(decode):
        tables.append(own[len(runs) + i])
        lens.append(limit)
    return (jnp.asarray(np.stack(tables), jnp.int32),
            jnp.asarray(lens, jnp.int32), n_chunk)


@pytest.mark.parametrize("window", [None, 6], ids=["whole", "window-6"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("group", [1, 4, 6, 9])
def test_query_tiles_attend_what_the_rows_attend(block, tile_rows, group,
                                                 dtype, window):
    """Prompt rows through query tiles, decode rows through the row walk,
    against the float32 reference: 1, 4, 6 and 9 query heads a K/V head,
    float32 and bf16 pools, with and without a lower bound a row (a window
    of 6 over contexts past it, the walk beginning mid-page). Padded rows
    are zero rows; the decode rows are the row walk's bit for bit."""
    kv_heads = 2
    k, v = pool(group, kv_heads, dtype)
    tables, lens, n_chunk = packing()
    q = queries(len(lens), group * kv_heads, seed=14).astype(dtype)
    starts = None if window is None else jnp.maximum(lens - window, 0)
    got = np.asarray(ragged_paged_attention(
        q, k, v, tables, lens, impl="pallas", layer=1, starts=starts,
        n_chunk=n_chunk), np.float32)
    want = np.asarray(ragged_paged_attention_reference(
        q, pa.kv_layer(k, 1), pa.kv_layer(v, 1), tables, lens,
        starts=starts))
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(got, want, atol=tol, rtol=tol)
    assert not got[np.asarray(lens) == 0].any(), "limit 0 is a zero row"
    by_row = np.asarray(ragged_paged_attention(
        q, k, v, tables, lens, impl="pallas", layer=1, starts=starts),
        np.float32)
    np.testing.assert_array_equal(got[n_chunk:], by_row[n_chunk:])
    np.testing.assert_allclose(got[:n_chunk], by_row[:n_chunk], atol=tol,
                               rtol=tol)


@pytest.mark.parametrize("kv_heads,dtype", [
    (1, jnp.bfloat16), (3, jnp.bfloat16), (3, jnp.float32),
    (2, jnp.float16)], ids=["1-bf16", "3-bf16", "3-f32", "2-f16"])
def test_a_prefill_program_is_all_tiles(block, tile_rows, kv_heads, dtype):
    """``n_chunk`` = all rows (a prefill program: no row walk at all), an
    odd number of K/V heads and a type without a strided load of its
    own."""
    k, v = pool(21, kv_heads, dtype)
    tables, lens, n_chunk = packing(decode=())
    assert n_chunk == len(lens)
    q = queries(n_chunk, 2 * kv_heads, seed=22).astype(dtype)
    got = np.asarray(ragged_paged_attention(
        q, k, v, tables, lens, impl="pallas", layer=0, n_chunk=n_chunk),
        np.float32)
    want = np.asarray(ragged_paged_attention_reference(
        q, pa.kv_layer(k, 0), pa.kv_layer(v, 0), tables, lens))
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(got, want, atol=tol, rtol=tol)


def jaxpr_text(fn, *args):
    import re
    return re.sub(r"0x[0-9a-f]+", "0x", str(jax.make_jaxpr(fn)(*args)))


def test_no_chunk_is_todays_program_bit_for_bit(block):
    """``n_chunk=0`` lowers to the same kernel as before the argument
    existed (every ``decode_fn``): the same jaxpr, the same bits, with and
    without a lower bound; with a chunk the program has both kernels."""
    k, v = pool(0, kv_heads=2)
    tables, lens, n_chunk = packing()
    q = queries(len(lens), 4)
    for starts in (None, jnp.maximum(lens - 6, 0)):
        def call(*a, **kw):
            return ragged_paged_attention(*a, impl="pallas", layer=2,
                                          starts=starts, **kw)
        without = jaxpr_text(call, q, k, v, tables, lens)
        assert jaxpr_text(lambda *a: call(*a, n_chunk=0),
                          q, k, v, tables, lens) == without
        assert "paged_attention_chunk" not in without
        tiled = jaxpr_text(lambda *a: call(*a, n_chunk=n_chunk),
                           q, k, v, tables, lens)
        assert "paged_attention_chunk" in tiled
        np.testing.assert_array_equal(
            np.asarray(call(q, k, v, tables, lens, n_chunk=0)),
            np.asarray(call(q, k, v, tables, lens)))
    # the gathered paths take the argument and ignore it
    for impl in ("xla", "reference"):
        np.testing.assert_array_equal(
            np.asarray(ragged_paged_attention(
                q, k, v, tables, lens, impl=impl, layer=2,
                n_chunk=n_chunk)),
            np.asarray(ragged_paged_attention(
                q, k, v, tables, lens, impl=impl, layer=2)))


def test_an_int8_pool_keeps_the_row_walk_under_a_chunk(block):
    """An int8 pool's prompt rows still answer, through the row walk: the
    program with ``n_chunk`` is the program without."""
    kf, vf = pool(7, kv_heads=2)
    kq, ks = quantize_kv(kf)
    vq, vs = quantize_kv(vf)
    tables, lens, n_chunk = packing()
    q = queries(len(lens), 4, seed=8)

    def call(*a, **kw):
        return ragged_paged_attention(
            a[0], QuantizedKV(a[1], a[2]), QuantizedKV(a[3], a[4]), a[5],
            a[6], impl="pallas", layer=2, **kw)

    args = (q, kq, ks, vq, vs, tables, lens)
    assert jaxpr_text(lambda *a: call(*a, n_chunk=n_chunk), *args) \
        == jaxpr_text(call, *args)
    got = np.asarray(call(*args, n_chunk=n_chunk))
    want = np.asarray(ragged_paged_attention_reference(
        q, QuantizedKV(kq[2], ks[2]), QuantizedKV(vq[2], vs[2]), tables,
        lens))
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


def test_a_tile_fetches_no_page_outside_its_rows_windows(block, tile_rows):
    """A windowed tile walks from its lowest ``starts`` to its highest
    limit: table entries wholly before or after point at pages full of NaN,
    and the result is finite and right."""
    k, v = pool(4, kv_heads=2)
    tables, lens, n_chunk = packing(decode=())
    lens = jnp.where(lens > 0, lens + 12, 0)       # contexts past the window
    q = queries(n_chunk, 12)
    starts = jnp.maximum(lens - 6, 0)
    want = dense_window(q, k, v, tables, lens, starts, layer=0)
    poisoned = PAGES - 1
    k = k.at[:, poisoned].set(jnp.nan)
    v = v.at[:, poisoned].set(jnp.nan)
    tables, lo, hi = np.asarray(tables).copy(), {}, {}
    for r in range(n_chunk):                       # a sequence's rows
        key = tuple(tables[r])
        lo[key] = min(lo.get(key, MAX_LEN), int(starts[r]))
        hi[key] = max(hi.get(key, 0), int(lens[r]))
    for r in range(n_chunk):
        key = tuple(tables[r])
        if hi[key]:
            tables[r, :lo[key] // PS] = poisoned
            tables[r, -(-hi[key] // PS):] = poisoned
    got = ragged_paged_attention(q, k, v, jnp.asarray(tables), lens,
                                 impl="pallas", layer=0, starts=starts,
                                 n_chunk=n_chunk)
    np.testing.assert_allclose(np.asarray(got), want, atol=2e-5, rtol=2e-5)


# -- the fetch count: the kernel's plan and the pool's counter ---------------

def plan_pages(tables, lens, starts, qb):
    """Pages the kernel's plan fetches for a chunk: the sum over its tiles,
    from the arrays the program sees (sequences told apart by their
    tables, as :func:`_paged_attention_chunk_call` does)."""
    pad = -len(lens) % qb
    lens = jnp.pad(lens, (0, pad))
    starts = jnp.pad(starts, (0, pad))
    new_seq = jnp.pad(jnp.any(tables[1:] != tables[:-1], axis=1), (1, pad),
                      constant_values=True)
    head, _, first, last = pa.chunk_tiles(new_seq, lens, starts, PS, qb)
    return int(jnp.sum(jnp.where(head, last - first, 0)))


@pytest.mark.parametrize("window", [None, 6], ids=["plain", "window-6"])
def test_the_pools_counter_reads_what_the_plan_fetches(tile_rows, window):
    """``PagePool.pages_touched`` reckons a call's prompt rows by tile with
    the kernel's own arithmetic (``chunk_tiles``): its READ for a packing
    is what the plan fetches for it plus a decode row's pages each, for a
    plain group and a window group; the gathered path's READ stands."""
    from paddle_tpu.inference.page_pool import (CacheGroup, ChunkRows,
                                                PagePool)
    tables, lens, n_chunk = packing()
    lens = jnp.where(lens > 0, lens + 12, 0)
    starts = jnp.maximum(lens - (window or MAX_LEN + 12), 0)
    pool_ = PagePool([CacheGroup("g", LAYERS, 2, D, window)], PAGES, PS,
                     max_seqs=8, pages_per_seq=PAGES_PER_SEQ + 3,
                     kv_dtype="f32", prefill_chunk=n_chunk)
    host_lens = np.asarray(lens)
    seqs = np.asarray([0] * 11 + [1] + [2] * 6 + [-1] * 2)[None]
    chunk = ChunkRows(seqs, host_lens[None, :n_chunk])
    decode = [(3 + i, int(n)) for i, n in enumerate(host_lens[n_chunk:])
              if n]
    rows = len(host_lens)
    got = pool_.pages_touched([(decode, rows, "pallas", chunk)])["g"]
    qb = pa.chunk_tile_rows(n_chunk)
    fetched = plan_pages(tables[:n_chunk], lens[:n_chunk], starts[:n_chunk],
                         qb)
    walked = sum(-(-n // PS) - int(s) // PS
                 for _, n in decode
                 for s in [max(n - window, 0) if window else 0])
    assert got["read"] == fetched + walked
    by_row = pool_.pages_touched([(decode + chunk.live_rows(), rows,
                                   "pallas")])["g"]
    assert by_row["live"] == got["live"]
    assert by_row["read"] > got["read"]
    gathered = pool_.pages_touched([(decode, rows, "xla", chunk)])["g"]
    assert gathered == {"read": rows * (PAGES_PER_SEQ + 3),
                        "live": got["live"]}


def test_a_mixed_ticks_chunk_reads_its_sequence_once_a_tile():
    """A ``_MixedTick``-shaped packing at ``agent_closed_swa``'s size: 256
    prompt rows of one 4k-token sequence and 32 decode rows. By tile the
    kernel reads under 1.5 x the live pages, in a plain group and in a
    window group; a walk a row read the chunk's sequence 256 times over
    (the ledger's 3.59 x in the cell)."""
    from paddle_tpu.inference.page_pool import (CacheGroup, ChunkRows,
                                                PagePool)
    pool_ = PagePool([CacheGroup("full", 1, 1, 8), CacheGroup("window", 1,
                                                              1, 8, 512)],
                     64, 16, max_seqs=33, pages_per_seq=576,
                     kv_dtype="f32", prefill_chunk=256)
    limits = np.arange(3841, 4097)[None]
    chunk = ChunkRows(np.zeros_like(limits), limits)
    decode = [(1 + i, int(n))
              for i, n in enumerate(np.linspace(700, 8000, 32))]
    by_tile = pool_.pages_touched([(decode, 288, "pallas", chunk)])
    by_row = pool_.pages_touched([(decode + chunk.live_rows(), 288,
                                   "pallas")])
    for name in ("full", "window"):
        assert by_tile[name]["live"] == by_row[name]["live"]
        assert by_tile[name]["read"] < 1.5 * by_tile[name]["live"]
    read, live = (sum(g[key] for g in by_row.values())
                  for key in ("read", "live"))
    assert read > 3.5 * live
    # eight tiles of 32 rows, each to its own highest limit's page: 242,
    # 244, ... 256 pages, where the sequence's 256 are live
    assert by_tile["full"]["read"] - by_tile["full"]["live"] \
        == sum(range(242, 257, 2)) - 256


@pytest.mark.parametrize("heads,kv_heads,bound,digest", [
    (4, 4, False, "377f69c93ec7f687"), (12, 2, True, "dcc9b006dfd50196")],
    ids=["vpu-fold", "mxu-fold-with-a-lower-bound"])
def test_a_call_without_prompt_rows_is_the_program_of_pr_35(
        monkeypatch, heads, kv_heads, bound, digest):
    """Every ``decode_fn`` calls the kernel with ``n_chunk`` 0: the jaxpr of
    that call (the row walk, compiled, not interpreted) is, to the letter,
    the one the commit before the tile path traced (PR 36's parent, digests
    taken from a checkout of it). A later edit to the row walk moves these
    on purpose and names itself here: PR 41 moved the second (from
    ``ed082b9229dd3334``), the fold for more than ``_VPU_GROUP_ROWS`` query
    heads a K/V head, which now reads its operands in the pool's type and
    takes a block at a time; the VPU fold's is PR 36's parent's still."""
    import hashlib
    monkeypatch.setattr(
        importlib.import_module("paddle_tpu.ops.flash_attention"),
        "INTERPRET", False)
    k, v = pool(0, kv_heads)
    lens = jnp.asarray([9, MAX_LEN, 1, 0, 17], jnp.int32)
    text = jaxpr_text(
        lambda *a: ragged_paged_attention(
            *a[:5], impl="pallas", layer=2, starts=a[5] if bound else None,
            n_chunk=0), queries(5, heads), k, v, tables_for(5), lens, lens)
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == digest


def _engine_program_texts(net, slots=4, chunk=8):
    """The jaxpr text of an engine's decode and mixed programs at a tiny
    size, addresses blanked."""
    import re
    from paddle_tpu.inference import llm
    eng = llm.LLMEngine(net, max_seqs=slots, page_size=4, num_pages=33,
                        max_len=32, prefill_chunk=chunk)
    try:
        ints = np.zeros((slots,), np.int32)
        decode = jax.make_jaxpr(eng._decode_fn)(
            eng._params, eng._buffers, eng._tokens_dev,
            eng._stage_decode(ints, ints), eng.k_pages, eng.v_pages,
            eng._key, *eng._state_args())
        rows = np.zeros((1, chunk), np.int32)
        per_slot = np.zeros((1, slots), np.int32)
        xs = {"tok": rows, "pos": rows, "lim": rows,
              "tbl": np.zeros((1, chunk, eng.pages_per_seq), np.int32),
              "fin": per_slot.astype(bool), "row": per_slot,
              "fpos": per_slot, "grant": per_slot}
        mixed = jax.make_jaxpr(eng._mixed_fn, static_argnums=8)(
            eng._params, eng._buffers, eng._new_carry(ints, ints), xs,
            eng.block_tables, eng.temperatures, eng._nonces, eng._key, 1)
    finally:
        eng.close()
    return {name: re.sub(r"0x[0-9a-f]+", "0x", str(jaxpr))
            for name, jaxpr in (("decode", decode), ("mixed", mixed))}


@pytest.mark.parametrize("model,program,digest", [
    ("gpt", "decode", "152fcfde09ee1bca"), ("gpt", "mixed", "95bc8fdf50841c06"),
    ("ouro", "decode", "d7593f6fdabc7440"),
    ("ouro", "mixed", "6b661ad679cb8065")])
def test_a_model_without_routed_experts_traces_to_the_program_of_pr_37(
        model, program, digest):
    """PR 38 rewrote the routed layer's combine (``nn/layers/
    dropless_moe.py``). ``models/gpt.py`` and ``models/ouro.py`` hold no
    routed layer: their engine programs trace, to the letter, to what the
    commit before traced (digests taken from a checkout of it). A later edit
    to the engine's programs or to these models moves them on purpose and
    names itself here. PR 44 moved the two ``decode`` digests: ``decode_fn``
    takes its host arrays as one staged vector and cuts it (slices, reshapes,
    one bitcast) in front of the same ``_PagedDecode.forward``; the ``mixed``
    digests are PR 37's still."""
    import hashlib
    import paddle_tpu as pt
    from paddle_tpu.models import (GPTConfig, GPTForCausalLM, OuroConfig,
                                   OuroForCausalLM)
    pt.seed(0)
    if model == "gpt":
        net = GPTForCausalLM(GPTConfig(
            vocab_size=64, hidden_size=32, num_layers=2, num_heads=2,
            max_position_embeddings=32))
    else:
        net = OuroForCausalLM(OuroConfig(
            num_hidden_layers=2, vocab_size=64, hidden_size=32,
            num_attention_heads=2, num_key_value_heads=2,
            intermediate_size=64, max_position_embeddings=32))
    net.eval()
    text = _engine_program_texts(net)[program]
    assert "moe" not in text
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == digest


# -- a latent pool: one row a token, no V, every head attends the same row ----

LW, LV, LHEADS = 24, 16, 4          # row width, value columns, query heads


def latent_pool(seed, dtype=jnp.float32):
    return jax.random.normal(jax.random.PRNGKey(seed),
                             (LAYERS, PAGES, PS, LW)).astype(dtype)


def dense_latent(q, pages, tables, lens, layer, starts=None):
    """The definition: every head's row against the sequence's rows, the
    probabilities over the rows' first ``LV`` columns."""
    tables, out = np.asarray(tables), np.zeros(q.shape[:2] + (LV,))
    pages = np.asarray(pages, np.float64)
    for r in range(q.shape[0]):
        lo = 0 if starts is None else int(starts[r])
        hi = int(lens[r])
        if hi <= lo:
            continue
        rows = pages[layer, tables[r]].reshape(-1, LW)[lo:hi]
        s = np.asarray(q[r], np.float64) @ rows.T / np.sqrt(LW)
        p = np.exp(s - s.max(-1, keepdims=True))
        out[r] = (p / p.sum(-1, keepdims=True)) @ rows[:, :LV]
    return out


@pytest.mark.parametrize("impl", ["pallas", "xla", "reference"])
def test_a_latent_pool_through_the_row_walk_and_the_gathered_paths(block,
                                                                   impl):
    """Ragged limits in one call, over the stacked pool with no V: limit 0,
    1, a page, a block and one over, ``max_len``."""
    span = block * PS
    lens = [0, 1, PS, PS + 1, 0, min(span + 1, MAX_LEN), MAX_LEN, 3, 0]
    pages = latent_pool(3)
    q = jax.random.normal(jax.random.PRNGKey(4), (len(lens), LHEADS, LW))
    tables = tables_for(len(lens), seed=5)
    lens = jnp.asarray(lens, jnp.int32)
    got = np.asarray(ragged_paged_attention(
        q, pages, None, tables, lens, impl=impl, layer=1, value_dim=LV))
    assert got.shape == (len(lens), LHEADS, LV)
    np.testing.assert_allclose(got, dense_latent(q, pages, tables, lens, 1),
                               atol=2e-5, rtol=2e-5)
    assert not got[np.asarray(lens) == 0].any(), "limit 0 is a zero row"
    if impl == "pallas":        # and they are not another layer's rows
        other = dense_latent(q, pages, tables, lens, 0)
        assert np.abs(got - other).max() > 1e-2


@pytest.mark.parametrize("bound", [False, True], ids=["whole", "windowed"])
def test_a_latent_pools_prompt_rows_through_query_tiles(block, bound):
    """A mixed tick's batch: 14 rows of one prompt at positions 11..24 (a
    tile and a half of 8 rows), 3 of another from position 0, padded rows,
    then decode rows; each page fetched once a tile, for the scores and for
    the values."""
    chunk_lens = list(range(12, 26)) + [1, 2, 3] + [0, 0, 0]
    tables = np.asarray(tables_for(5, seed=7))
    rows = np.concatenate([np.repeat(tables[:1], 14, 0),
                           np.repeat(tables[1:2], 3, 0),
                           np.zeros((3, PAGES_PER_SEQ), np.int32),
                           tables[2:5]])
    lens = jnp.asarray(chunk_lens + [9, MAX_LEN, 0], jnp.int32)
    starts = jnp.maximum(lens - 10, 0) if bound else None
    pages = latent_pool(8)
    q = jax.random.normal(jax.random.PRNGKey(9),
                          (len(lens), LHEADS, LW))
    got = np.asarray(ragged_paged_attention(
        q, pages, None, jnp.asarray(rows), lens, impl="pallas", layer=2,
        starts=starts, n_chunk=len(chunk_lens), value_dim=LV))
    np.testing.assert_allclose(
        got, dense_latent(q, pages, rows, lens, 2, starts), atol=2e-5,
        rtol=2e-5)
    walked = np.asarray(ragged_paged_attention(
        q, pages, None, jnp.asarray(rows), lens, impl="pallas", layer=2,
        starts=starts, value_dim=LV))
    np.testing.assert_allclose(got, walked, atol=2e-5, rtol=2e-5)


def test_a_bf16_latent_pool_rounds_as_a_bf16_pool_does():
    pages = latent_pool(11, jnp.bfloat16)
    q = jax.random.normal(jax.random.PRNGKey(12), (4, LHEADS, LW)) \
        .astype(jnp.bfloat16)
    tables = tables_for(4, seed=13)
    lens = jnp.asarray([MAX_LEN, 7, 19, 1], jnp.int32)
    got = np.asarray(ragged_paged_attention(
        q, pages, None, tables, lens, impl="pallas", layer=0,
        n_chunk=0, value_dim=LV), np.float32)
    want = dense_latent(np.asarray(q, np.float32), pages, tables, lens, 0)
    np.testing.assert_allclose(got, want, atol=3e-2, rtol=3e-2)


def test_a_latent_pool_is_copied_once_a_page_not_twice(monkeypatch):
    """The kernels' own text: one page buffer and one copy a page (a K/V
    pool's has two of each), so ``kv_pages_read x page_bytes`` is what
    crosses HBM."""
    monkeypatch.setattr(
        importlib.import_module("paddle_tpu.ops.flash_attention"),
        "INTERPRET", False)
    lens = jnp.asarray([9, MAX_LEN, 1, 0, 17], jnp.int32)
    q = jax.random.normal(jax.random.PRNGKey(1), (5, LHEADS, LW))

    def copies(text):
        return text.count("dma_start")

    for n_chunk in (0, 5):
        latent = jaxpr_text(
            lambda q, p, t, n: ragged_paged_attention(
                q, p, None, t, n, impl="pallas", layer=2, n_chunk=n_chunk,
                value_dim=LV), q, latent_pool(0), tables_for(5), lens)
        k, v = pool(0, 1)
        both = jaxpr_text(
            lambda q, k, v, t, n: ragged_paged_attention(
                q, k, v, t, n, impl="pallas", layer=2, n_chunk=n_chunk),
            queries(5, LHEADS), k, v, tables_for(5), lens)
        assert copies(latent) > 0 and 2 * copies(latent) == copies(both)


def test_a_latent_pool_needs_its_value_width_and_has_no_int8_form():
    q = jax.random.normal(jax.random.PRNGKey(1), (2, LHEADS, LW))
    lens = jnp.asarray([3, 4], jnp.int32)
    for impl in ("pallas", "xla"):
        with pytest.raises(ValueError, match="latent pool"):
            ragged_paged_attention(q, latent_pool(0), None, tables_for(2),
                                   lens, impl=impl, layer=0)
    quantized = pa.kv_zeros((LAYERS, PAGES, PS, 1, LW), "int8")
    with pytest.raises(ValueError, match="latent pool"):
        ragged_paged_attention(q, quantized, None, tables_for(2), lens,
                               layer=0, value_dim=LV)


def test_block_of_a_latent_pool_follows_the_stored_row():
    """A page without a head axis is ``[page_size, width]`` in VMEM: its
    tokens on the sublanes, its width padded to whole lanes; ONE pool's
    buffers, so twice the pages a K/V pool of the same bytes moves."""
    assert pa._pages_per_block(16, None, 640, jnp.bfloat16, 480, 1) == 102
    assert pa._pages_per_block(16, None, 576, jnp.bfloat16, 480, 1) == 102
    assert pa._pages_per_block(16, None, 640, jnp.bfloat16, 8, 1) == 8
    assert pa._pages_per_block(16, 5, 128, jnp.bfloat16, 480, 2) \
        == pa._pages_per_block(16, 5, 128, jnp.bfloat16, 480)


# -- a sink in the softmax, and values of another width than the keys --------

DV, SINK_WINDOW = 8, 8      # a V row's width beside keys of D; a window


@pytest.fixture
def let_go():
    """Each case below is an interpreted kernel of its own shapes that no
    other test runs again: let the executables go (as ``mxu_fold`` does;
    with them all kept, a process that runs this file whole dies in
    XLA:CPU's compiler)."""
    yield
    jax.clear_caches()


def sunk_pool(seed, kv_heads, dv, dtype=jnp.float32):
    k = jax.random.normal(jax.random.PRNGKey(seed),
                          (LAYERS, PAGES, PS, kv_heads, D))
    v = jax.random.normal(jax.random.PRNGKey(seed + 100),
                          (LAYERS, PAGES, PS, kv_heads, dv))
    return k.astype(dtype), v.astype(dtype)


def dense_sink(q, k, v, tables, lens, starts, sinks, layer):
    """The definition, a row and a head at a time, in float64: ``o_h = sum_j
    e^{s_hj - m} v_j / (e^{b_h - m} + sum_j e^{s_hj - m})`` over positions
    ``starts[r] <= j < lens[r]`` (no sink: the plain softmax)."""
    q, k, v = (np.asarray(a, np.float64) for a in (q, k, v))
    tables = np.asarray(tables)
    out = np.zeros(q.shape[:2] + (v.shape[-1],))
    group = q.shape[1] // k.shape[3]
    for r in range(q.shape[0]):
        lo = 0 if starts is None else int(starts[r])
        hi = int(lens[r])
        if hi == 0:
            continue
        kk = k[layer, tables[r]].reshape(-1, k.shape[3], k.shape[-1])[lo:hi]
        vv = v[layer, tables[r]].reshape(-1, v.shape[3], v.shape[-1])[lo:hi]
        for h in range(q.shape[1]):
            s = kk[:, h // group] @ q[r, h] / np.sqrt(q.shape[-1])
            b = -np.inf if sinks is None else float(sinks[h])
            m = max(s.max(), b)
            p = np.exp(s - m)
            out[r, h] = (p / (p.sum() + np.exp(b - m))) @ vv[:, h // group]
    return out


SINK_LENS = [0, 1, PS - 1, SINK_WINDOW, SINK_WINDOW + 1, 22, MAX_LEN]


@pytest.mark.parametrize("bound", [False, True], ids=["whole", "windowed"])
@pytest.mark.parametrize("dv", [D, DV], ids=["dv=dk", "dv<dk"])
@pytest.mark.parametrize("heads,kv_heads", [(4, 2), (12, 2)],
                         ids=["vpu-fold", "mxu-fold"])
@pytest.mark.parametrize("sunk", [True, False], ids=["sink", "no-sink"])
def test_a_sink_and_unequal_widths_in_the_row_walk_and_both_gathered_paths(
        block, let_go, heads, kv_heads, dv, bound, sunk):
    """Rows of 0 / 1 / a page less one / a window / a window and one tokens
    (and two longer ones), with a lower bound a row and without: the row
    walk (the VPU fold at 2 query heads a K/V head, the MXU fold at 6), the
    gathered path and the float32 reference agree with the definition, with
    a sink a query head and without, with V as wide as K and narrower. A
    sink as large as the scores takes a visible share: the row of one token
    is NOT its value."""
    if not sunk and dv == D:
        pytest.skip("neither a sink nor unequal widths: the tests above")
    k, v = sunk_pool(21, kv_heads, dv)
    lens = jnp.asarray(SINK_LENS, jnp.int32)
    starts = jnp.maximum(lens - SINK_WINDOW, 0) if bound else None
    rows = lens.shape[0]
    q = queries(rows, heads, seed=22)
    sinks = jax.random.normal(jax.random.PRNGKey(23), (heads,)) \
        if sunk else None
    tables = tables_for(rows, seed=24)
    want = dense_sink(q, k, v, tables, lens, starts, sinks, layer=1)
    for impl in ("pallas", "xla", "reference"):
        got = np.asarray(ragged_paged_attention(
            q, k, v, tables, lens, impl=impl, layer=1, starts=starts,
            sinks=sinks))
        assert got.shape == (rows, heads, dv)
        np.testing.assert_allclose(got, want, atol=3e-5, rtol=3e-5,
                                   err_msg=impl)
        assert not got[0].any(), "limit 0 is a zero row, sink or none"
    if sunk:
        one = np.asarray(v[1, tables[1, 0], 0])        # row 1: one token
        group = heads // kv_heads
        assert np.abs(want[1] - np.repeat(one, group, 0)).max() > 0.05


@pytest.mark.parametrize("window", [None, 6], ids=["whole", "window-6"])
@pytest.mark.parametrize("dv", [D, DV], ids=["dv=dk", "dv<dk"])
@pytest.mark.parametrize("heads,kv_heads", [(4, 2), (12, 2)],
                         ids=["2-a-kv-head", "6-a-kv-head"])
def test_a_sink_and_unequal_widths_in_the_query_tiles(
        tile_rows, let_go, heads, kv_heads, dv, window):
    """The mixed tick's packing of the tile tests (a run cut by a window's
    edge, a run of one row, padded rows, decode rows behind them) with a
    sink a head and V narrower than K: tiles and row walk together agree
    with the definition, with a window shorter than the chunk's longest run
    and without."""
    tables, lens, n_chunk = packing()
    k, v = sunk_pool(25, kv_heads, dv)
    q = queries(lens.shape[0], heads, seed=26)
    sinks = jax.random.normal(jax.random.PRNGKey(27), (heads,))
    starts = None if window is None else jnp.maximum(lens - window, 0)
    want = dense_sink(q, k, v, tables, lens, starts, sinks, layer=2)
    got = np.asarray(ragged_paged_attention(
        q, k, v, tables, lens, impl="pallas", layer=2, starts=starts,
        n_chunk=n_chunk, sinks=sinks))
    np.testing.assert_allclose(got, want, atol=3e-5, rtol=3e-5)
    walked = np.asarray(ragged_paged_attention(
        q, k, v, tables, lens, impl="pallas", layer=2, starts=starts,
        sinks=sinks))
    np.testing.assert_allclose(got, walked, atol=3e-5, rtol=3e-5)
    assert not got[np.asarray(lens) == 0].any()


def test_bf16_pages_with_a_sink_lie_within_bf16_of_the_reference(block,
                                                                 let_go):
    """The MXU fold and the tiles round queries and probabilities to the
    pool's type; the sink stays float32 in the state."""
    tables, lens, n_chunk = packing()
    k, v = sunk_pool(28, 2, DV, jnp.bfloat16)
    q = queries(lens.shape[0], 12, seed=29).astype(jnp.bfloat16)
    sinks = jax.random.normal(jax.random.PRNGKey(30), (12,))
    starts = jnp.maximum(lens - 6, 0)
    want = np.asarray(ragged_paged_attention_reference(
        q, pa.kv_layer(k, 0), pa.kv_layer(v, 0), tables, lens, starts=starts,
        sinks=sinks))
    got = np.asarray(ragged_paged_attention(
        q, k, v, tables, lens, impl="pallas", layer=0, starts=starts,
        n_chunk=n_chunk, sinks=sinks), np.float32)
    assert np.abs(got - want).max() < 2e-2


def test_no_sink_and_equal_widths_is_todays_program_to_the_letter():
    """``sinks=None`` over K and V of one width traces to the jaxpr of a
    call that never heard of either, on the kernel path (row walk and tiles)
    and the gathered one; a sink changes it."""
    tables, lens, n_chunk = packing()
    k, v = pool(0, kv_heads=2)
    q = queries(lens.shape[0], 12)

    def text(**kw):
        return {impl: jaxpr_text(lambda *a: ragged_paged_attention(
            *a, impl=impl, layer=1, n_chunk=n_chunk, **kw),
            q, k, v, tables, lens) for impl in ("pallas", "xla")}

    assert text(sinks=None) == text()
    sunk = text(sinks=jnp.zeros((12,)))
    assert all(sunk[impl] != text()[impl] for impl in sunk)


@pytest.mark.parametrize("impl", ["pallas", "xla", "reference"])
def test_what_no_path_serves_is_refused_by_name(impl):
    """An int8 pool with a sink or with unequal widths, a latent pool with a
    sink: a ``ValueError`` that names it, on every path."""
    k, v = sunk_pool(31, 2, D)
    kq = QuantizedKV(*quantize_kv(k))
    lens = jnp.asarray([5, 9], jnp.int32)
    q, tables = queries(2, 4), tables_for(2)
    with pytest.raises(ValueError, match="sinks over an int8 pool"):
        ragged_paged_attention(q, kq, QuantizedKV(*quantize_kv(v)), tables,
                               lens, impl=impl, layer=0,
                               sinks=jnp.zeros((4,)))
    narrow = QuantizedKV(*quantize_kv(sunk_pool(31, 2, DV)[1]))
    with pytest.raises(ValueError, match="keys of 16 beside values of 8"):
        ragged_paged_attention(q, kq, narrow, tables, lens, impl=impl,
                               layer=0)
    with pytest.raises(ValueError, match="sinks over a latent pool"):
        ragged_paged_attention(
            jnp.ones((2, LHEADS, LW)),
            latent_pool(1), None, tables, lens, impl=impl, layer=0,
            value_dim=LV, sinks=jnp.zeros((LHEADS,)))


@pytest.mark.parametrize("kv_heads,group", [(2, 6), (1, 4)],
                         ids=["2-kv-heads", "1-kv-head"])
@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 3e-5),
                                       (jnp.bfloat16, 3e-2)],
                         ids=["f32", "bf16"])
def test_keys_of_two_lane_tiles_beside_values_of_one(tile_rows, let_go,
                                                     kv_heads, group, dtype,
                                                     tol):
    """Keys 256 wide (192 stored in whole lanes: the sink model's) beside
    values of 128: the query tiles keep one K page buffer a lane tile (their
    strided loads want rows of one tile) and put a head's key rows together
    again; the row walk keeps one buffer. With a sink and a window."""
    dk, dv = 256, 128
    tables, lens, n_chunk = packing()
    k = jax.random.normal(jax.random.PRNGKey(40),
                          (LAYERS, PAGES, PS, kv_heads, dk)).astype(dtype)
    v = jax.random.normal(jax.random.PRNGKey(41),
                          (LAYERS, PAGES, PS, kv_heads, dv)).astype(dtype)
    heads = kv_heads * group
    q = (jax.random.normal(jax.random.PRNGKey(42),
                           (lens.shape[0], heads, dk)) / 4).astype(dtype)
    sinks = jax.random.normal(jax.random.PRNGKey(43), (heads,))
    starts = jnp.maximum(lens - 6, 0)
    want = dense_sink(q, k, v, tables, lens, starts, sinks, layer=1)
    got = np.asarray(ragged_paged_attention(
        q, k, v, tables, lens, impl="pallas", layer=1, starts=starts,
        n_chunk=n_chunk, sinks=sinks), np.float32)
    assert got.shape == (lens.shape[0], heads, dv)
    np.testing.assert_allclose(got, want, atol=tol, rtol=tol)


# -- a head count that is no multiple of the sublane tile: 6 and 30 K/V heads --
# -- in pages of 8 and 32, the zero heads the MODEL's (models/olmo_hybrid.py) --

def _stored(kv_heads, group, seed=21):
    """``(mixer, q, k_rows, v_rows, k_pool, v_pool, tables, lens,
    n_chunk)``: a mixed tick's rows (:func:`packing`) of ``kv_heads`` K/V
    heads, padded by the model's ``FullAttention.stored`` and WRITTEN
    through ``kv_write`` into a stacked pool of the heads the model's
    ``kv_cache_spec()`` names, each row at its own position. ``q`` comes
    back padded, the K and V rows as the model has them."""
    from paddle_tpu.models.olmo_hybrid import (FullAttention,
                                               OlmoHybridConfig)
    heads = group * kv_heads
    mixer = FullAttention(OlmoHybridConfig(
        hidden_size=heads * D, num_attention_heads=heads,
        num_key_value_heads=kv_heads, num_layers=4))
    stored = mixer.cfg.stored_kv_heads
    tables, lens, n_chunk = packing()
    q = queries(len(lens), heads, seed=seed)
    # every position of every row's table holds rows of its own
    kk, vv = jax.random.normal(jax.random.PRNGKey(seed + 1),
                               (2, PAGES * PS, kv_heads, D))
    q, k_rows, v_rows = mixer.stored(q, kk, vv)
    assert q.shape[1] == group * stored
    assert k_rows.shape == v_rows.shape == (PAGES * PS, stored, D)
    zeros = jnp.zeros((LAYERS, PAGES, PS, stored, D))
    page, off = (t.reshape(-1) for t in jnp.meshgrid(
        jnp.arange(PAGES), jnp.arange(PS), indexing="ij"))
    k_pool = pa.kv_write(zeros, 1, page, off, k_rows)
    v_pool = pa.kv_write(zeros, 1, page, off, v_rows)
    shape = (PAGES, PS, kv_heads, D)
    return (mixer, q, kk.reshape(shape), vv.reshape(shape), k_pool, v_pool,
            tables, lens, n_chunk)


@pytest.mark.parametrize("kv_heads,group", [(6, 1), (30, 1), (6, 2)],
                         ids=["6-mha", "30-mha", "6-gqa-2"])
@pytest.mark.parametrize("impl", ["pallas", "xla", "reference"])
def test_heads_stored_in_whole_tiles_attend_as_the_heads_written(
        block, tile_rows, impl, kv_heads, group):
    """6 and 30 K/V heads through the row walk, the query tiles and both
    gathered paths, over pages of 8 and 32: the model pads q, k and v with
    zero heads (a whole group of query heads a stored head) and cuts the
    op's output; the pool, ``kv_write`` and the op know the stored heads
    alone. Against the reference over a pool of the heads as written."""
    mixer, q, kk, vv, k_pool, v_pool, tables, lens, n_chunk = _stored(
        kv_heads, group)
    stored = k_pool.shape[-2]
    heads = group * kv_heads
    assert stored % 8 == 0 and stored > kv_heads
    assert not np.asarray(k_pool[1, :, :, kv_heads:]).any()
    assert not np.asarray(k_pool[0]).any()
    att = np.asarray(ragged_paged_attention(
        q, k_pool, v_pool, tables, lens, impl=impl, layer=1,
        n_chunk=n_chunk))
    assert att.shape == (len(lens), group * stored, D)
    assert not att[:, heads:].any(), "a zero head attends zero heads"
    got = att[:, :heads]
    want = np.asarray(ragged_paged_attention_reference(
        q[:, :heads], kk, vv, tables, lens))
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)
    assert not got[np.asarray(lens) == 0].any(), "limit 0 is a zero row"
    # what ``project`` hands the output projection is the cut
    cut = mixer.project(jnp.asarray(att))
    np.testing.assert_array_equal(
        np.asarray(cut), np.asarray(mixer.o_proj(
            jnp.asarray(got).reshape(-1, heads * D))))


@pytest.mark.parametrize("heads,kv_heads", [(12, 12), (20, 20), (25, 25),
                                            (12, 6)],
                         ids=["12-mha", "20-mha", "25-mha", "12-over-6"])
def test_the_gathered_path_serves_a_pool_of_any_head_count(heads, kv_heads):
    """GPT-2's published head counts (12, 20, 25) and a 6-head GQA group:
    the pool stores the heads it is told, ``kv_write`` refuses nothing and
    pads nothing, and the gathered path attends them as the reference."""
    k, v = pool(5, kv_heads)
    tables, lens, n_chunk = packing()
    q = queries(len(lens), heads)
    rows = jax.random.normal(jax.random.PRNGKey(8), (len(lens), kv_heads, D))
    assert pa.kv_write(k, 1, tables[:, 0], lens % PS, rows).shape == k.shape
    got = np.asarray(ragged_paged_attention(
        q, k, v, tables, lens, impl="xla", layer=1, n_chunk=n_chunk))
    np.testing.assert_allclose(got, reference(q, k, v, tables, lens, 1),
                               atol=2e-5, rtol=2e-5)
