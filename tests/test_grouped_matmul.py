"""ops/grouped_matmul.py (the Pallas grouped product, through the
interpreter on the CPU) against ``jax.lax.ragged_dot``, its oracle: the same
products over the rows that belong to a group, whatever the alignment of
the groups to the kernel's tiles, and each group's weights copied once.

What the interpreter cannot show (the copies' speed, VMEM) is held by
tests/test_chip_compile.py, which compiles the kernel for a described v5e
at the cell's shapes, and on the chip by ``chip_smoke.py``'s kernel phase
and the benchmark's ``correct``."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops import grouped_matmul as gm

ROWS, TM = 48, 8
# group sizes over 48 rows in row tiles of 8; names say what they hold
SIZES = {
    "all_equal": (8, 8, 8, 8, 8, 8),
    "one_group_holds_every_row": (0, 48, 0),
    "empty_first_last_and_middle": (0, 11, 0, 0, 21, 16, 0),
    "every_boundary_off_a_tile_edge": (3, 7, 9, 2, 13, 5, 6),
    "a_group_over_three_tiles": (5, 19, 1, 23),
    "tail_never_read_back": (6, 0, 9, 4),
    "no_group_has_a_row": (0, 0, 0),
    "single_rows": (1, 1, 1, 0, 1, 1),
}
# [K, N] of the layer's two matrices, in miniature: ``w_in`` is tall and
# has three column tiles, ``w_out`` is wide and has two
SHAPES = {"w_in": (64, 384, 128), "w_out": (16, 256, 128)}
TOL = {jnp.float32: 1e-5, jnp.bfloat16: 2e-2}


def operands(sizes, k, n, dtype, rows=ROWS, seed=0):
    key = jax.random.split(jax.random.PRNGKey(seed), 2)
    lhs = jax.random.normal(key[0], (rows, k)).astype(dtype)
    # poison the tail: a kernel that read it into a kept row would show
    held = int(sum(sizes))
    lhs = lhs.at[held:].set(jnp.nan)
    rhs = (jax.random.normal(key[1], (len(sizes), k, n))
           / np.sqrt(k)).astype(dtype)
    return lhs, rhs, jnp.asarray(sizes, jnp.int32), held


def check(got, lhs, rhs, sizes, held, dtype):
    want = jax.lax.ragged_dot(jnp.nan_to_num(lhs), rhs, sizes)
    assert got.shape == want.shape and got.dtype == want.dtype
    got = np.asarray(got[:held], np.float32)
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, np.asarray(want[:held], np.float32),
                               atol=TOL[dtype], rtol=TOL[dtype])


@pytest.mark.parametrize("weights", list(SHAPES))
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("sizes", list(SIZES))
def test_kernel_matches_ragged_dot(sizes, dtype, weights):
    k, n, tn = SHAPES[weights]
    lhs, rhs, gs, held = operands(SIZES[sizes], k, n, dtype)
    got = gm.grouped_matmul(lhs, rhs, gs, row_tile=TM, column_tile=tn,
                            interpret=True)
    check(got, lhs, rhs, gs, held, dtype)


@pytest.mark.parametrize("rows,row_tile", [(43, 8), (50, 16), (24, 128)],
                         ids=["43_by_8", "50_by_16", "24_under_one_tile"])
def test_a_row_count_that_is_no_multiple_of_the_row_tile(rows, row_tile):
    sizes = (5, 0, rows - 12, 4)
    lhs, rhs, gs, held = operands(sizes, 64, 128, jnp.float32, rows=rows)
    got = gm.grouped_matmul(lhs, rhs, gs, row_tile=row_tile, interpret=True)
    check(got, lhs, rhs, gs, held, jnp.float32)


@pytest.mark.parametrize("buffers", [2, 3, 5])
def test_any_ring_of_weight_buffers_gives_the_same_product(buffers):
    """The module's two slots (one copy in flight), three, and more slots
    than the call has tiles to copy."""
    sizes = SIZES["every_boundary_off_a_tile_edge"]
    lhs, rhs, gs, held = operands(sizes, 64, 256, jnp.float32)
    got = gm.grouped_matmul(lhs, rhs, gs, row_tile=TM, column_tile=128,
                            buffers=buffers, interpret=True)
    check(got, lhs, rhs, gs, held, jnp.float32)


def test_tiles_follow_the_static_shapes():
    """The layer's two matrices at the published widths: full-``K`` tiles
    of megabytes that divide the columns, whatever the row count."""
    assert gm._column_tile(4096, 1536, 2) == 1536       # w_in whole: 12.6 MB
    assert gm._column_tile(768, 4096, 2) == 4096        # w_out whole: 6.3 MB
    assert gm._column_tile(4096, 14336, 2) == 2048      # 16 MiB of a wide one
    assert gm._column_tile(64, 48, 4) == 48             # not whole lanes
    assert gm._column_tile(1 << 20, 256, 4) == 128      # never under a lane


@pytest.mark.parametrize("buffers", [2, 3])
@pytest.mark.parametrize("sizes", list(SIZES))
def test_each_group_with_rows_has_its_weight_tiles_copied_once(sizes,
                                                               buffers):
    """Walk the kernel's grid on the host with the kernel's own plan and
    its own rule (``weight_copies``): every column tile of every group
    that has a row is copied exactly once, however many row tiles the
    group straddles, nothing of a group without rows; a copy is started
    before the step that waits for it, and never into a slot whose tile
    is still due."""
    sizes = SIZES[sizes]
    tiles_n = 3
    _, next_live, group, tile, n_visits = (
        np.asarray(a) for a in gm.plan_visits(
            jnp.asarray(sizes, jnp.int32), ROWS, TM))
    n_visits = int(n_visits[0])
    # the visits: each (group, row tile) pair that shares a row, in order
    want = [(g, t) for g, (lo, hi) in enumerate(zip(
        np.cumsum(sizes) - sizes, np.cumsum(sizes)))
        for t in range(ROWS // TM) if max(lo, t * TM) < min(hi, (t + 1) * TM)]
    assert list(zip(group[:n_visits], tile[:n_visits])) == want
    # a step past the visits moves no block
    assert all((g, t) == want[-1] for g, t in
               zip(group[n_visits:], tile[n_visits:]) if want)
    live = [g for g, n in enumerate(sizes) if n]
    assert [int(next_live[i]) for i in range(len(sizes) + 1)] == [
        min([g for g in live if g >= i], default=len(sizes))
        for i in range(len(sizes) + 1)]

    in_slot = [None] * buffers          # the unit a slot holds or awaits
    copied, slot = [], 0
    for col in range(tiles_n):
        for s in range(n_visits):
            g = int(group[s])
            if s and g == group[s - 1]:
                assert in_slot[slot] == (g, col)    # multiplies what it holds
                continue
            first_unit = col == 0 and s == 0
            done = None if first_unit else in_slot[slot]
            slot = 0 if first_unit else (slot + 1) % buffers
            for ahead, (g2, c2, starts) in enumerate(gm.weight_copies(
                    col, g, first_unit, next_live, tiles_n, buffers)):
                if starts:
                    # only the slot of the unit just done may be taken
                    at = (slot + ahead) % buffers
                    assert in_slot[at] in (None, done)
                    in_slot[at] = (int(g2), int(c2))
                    copied.append(in_slot[at])
            assert in_slot[slot] == (g, col)        # waits for its own
    assert sorted(copied) == sorted((g, c) for g in live
                                    for c in range(tiles_n))
    assert len(set(copied)) == len(copied)


def test_one_trace_serves_every_layer(monkeypatch):
    """An engine program calls the kernel twice a layer with two shapes,
    the same in every layer: the jitted call is traced (and so lowered)
    once a shape for all of them."""
    sizes = SIZES["every_boundary_off_a_tile_edge"]
    lhs, w_in, gs, _ = operands(sizes, 64, 128, jnp.float32)
    _, w_out, _, _ = operands(sizes, 128, 64, jnp.float32, seed=1)
    traced = []
    real = gm.pl.pallas_call
    monkeypatch.setattr(gm.pl, "pallas_call",
                        lambda *a, **kw: traced.append(kw["name"])
                        or real(*a, **kw))
    gm._grouped_matmul_call.clear_cache()

    def layers(x):
        for _ in range(3):
            h = gm.grouped_matmul(x, w_in, gs, row_tile=TM, interpret=True)
            x = gm.grouped_matmul(h, w_out, gs, row_tile=TM, interpret=True)
        return x

    text = jax.jit(layers).lower(jnp.nan_to_num(lhs)).as_text()
    assert traced == ["grouped_matmul"] * 2      # the trace's name
    assert text.count("call @_grouped_matmul_call") == 6


def test_operands_that_are_not_a_grouped_product_are_refused():
    lhs, rhs, gs, _ = operands((8, 8), 64, 128, jnp.float32, rows=16)
    with pytest.raises(ValueError, match=r"\[M, K\], \[G, K, N\] and \[G\]"):
        gm.grouped_matmul(lhs[:, :32], rhs, gs, interpret=True)
    with pytest.raises(ValueError, match="lhs is"):
        gm.grouped_matmul(lhs.astype(jnp.bfloat16), rhs, gs, interpret=True)
    with pytest.raises(ValueError, match="does not divide"):
        gm.grouped_matmul(lhs, rhs, gs, column_tile=96, interpret=True)
    with pytest.raises(ValueError, match="two slots"):
        gm.grouped_matmul(lhs, rhs, gs, buffers=1, interpret=True)
