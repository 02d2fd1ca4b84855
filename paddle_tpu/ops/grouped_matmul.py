"""A grouped matrix product over rows sorted by group, as a Pallas kernel.

``grouped_matmul(lhs [M, K], rhs [G, K, N], group_sizes [G])`` is
``jax.lax.ragged_dot`` with the same contract: the first ``group_sizes[0]``
rows are multiplied by ``rhs[0]``, the next ``group_sizes[1]`` by ``rhs[1]``
and so on; rows past ``sum(group_sizes)`` come back unspecified. It is what
an engine's programs run for the routed experts where the weights live on a
TPU (``nn/layers/dropless_moe.py``); ``ragged_dot`` is the path anywhere
else and this kernel's oracle (tests/test_grouped_matmul.py).

A served batch gives an expert a handful of rows, so the product is a
stream of weights: the kernel is built so that every byte of a group's
weights crosses HBM once a call, and nothing of a group without rows.

- The rows are walked in aligned tiles of ``tm``. A VISIT is one (group, row
  tile) pair that share a row; a group whose rows straddle a tile's edge is
  visited once a tile, a tile that holds several groups once a group, and
  each visit stores only its own group's rows of the tile (a mask). A group
  without rows has no visit.
- The weights stay in HBM and come in through a ring of ``buffers`` VMEM
  slots, a full-``K`` ``[K, tn]`` tile (megabytes: a whole expert where it
  fits) a copy, ``buffers - 1`` copies in flight while one tile is
  multiplied. The grid is (column tile, visit), visits inner: a tile of
  weights is fetched at the first visit of its group in a sweep and serves
  every visit of that group, whatever the alignment of the group to the
  row tiles. ``lhs`` and the output move a row tile a step through Pallas'
  own pipeline; ``lhs`` is read once a column sweep.
- The arithmetic is ``ragged_dot``'s: the operands as they come, one
  float32 accumulation over the whole of ``K``, the result in the operands'
  dtype.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import _default_interpret

# a tile of weights in VMEM, at most (a whole expert of the hybrid cell:
# 12.6 MB of ``w_in``), and how many the ring holds. Read on the chip (PR
# 29): whole experts beat tiles of 4 MiB by 1-5% (``lhs`` is read once, a
# third of the grid steps), and two slots stream as fast as three or four.
_WEIGHT_TILE_BYTES = 16 << 20
_WEIGHT_BUFFERS = 2
_ROW_TILE = 128
_LANES = 128


def _column_tile(k: int, n: int, itemsize: int) -> int:
    """Columns of a ``[K, tn]`` weight tile: the widest divisor of ``n`` in
    whole lanes that ``_WEIGHT_TILE_BYTES`` hold (all 1,536 of the layer's
    ``[4096, 1536]`` bf16 and all 4,096 of its ``[768, 4096]``), all of an
    ``n`` that is not whole lanes."""
    if n % _LANES:
        return n
    fits = [c for c in range(_LANES, n + 1, _LANES)
            if n % c == 0 and k * c * itemsize <= _WEIGHT_TILE_BYTES]
    return max(fits, default=_LANES)


def plan_visits(group_sizes, m: int, tm: int):
    """The walk of one call, as the int32 vectors the kernel prefetches:
    ``(offsets [G + 1], next_live [G + 1], group [S], tile [S], n_visits
    [1])`` with ``S = tiles + G - 1`` grid steps, at least as many as there
    can be visits. Step ``s`` below ``n_visits`` multiplies row tile
    ``tile[s]`` by group ``group[s]``; a step past the visits names the
    last visit's blocks again, so nothing moves for it. ``next_live[i]``
    is the first group from ``i`` on that has rows (``G``: none).

    Sums over a comparison, not cumulative sums and gathers, each of which
    is several device operations of its own beside the kernel: these fuse
    into a handful."""
    i32 = jnp.int32
    g = group_sizes.shape[0]
    tiles_m = -(-m // tm)
    sizes = group_sizes.astype(i32)
    ids = jnp.arange(g, dtype=i32)
    upto = jnp.arange(g + 1, dtype=i32)[:, None]
    offsets = jnp.sum(jnp.where(ids[None, :] < upto, sizes[None, :], 0), 1)
    starts, ends = offsets[:-1], offsets[1:]
    has_rows = sizes > 0
    next_live = jnp.min(
        jnp.where(has_rows[None, :] & (ids[None, :] >= upto), ids[None, :],
                  g), axis=1)
    tiles = jnp.where(has_rows, (ends - 1) // tm - starts // tm + 1, 0)
    visit_end = jnp.sum(
        jnp.where(ids[None, :] <= ids[:, None], tiles[None, :], 0), axis=1)
    n_visits = jnp.sum(tiles, keepdims=True)
    step = jnp.minimum(jnp.arange(tiles_m + g - 1, dtype=i32),
                       jnp.maximum(n_visits - 1, 0))[:, None]
    group = jnp.minimum(jnp.sum(step >= visit_end[None, :], axis=1), g - 1)
    # the visits take the row tiles in turn; only a group that starts
    # inside a tile begins on the tile of the visit before it
    shares = has_rows & (starts % tm != 0)
    tile = step[:, 0] - jnp.sum(
        shares[None, :] & (step >= (visit_end - tiles)[None, :]), axis=1)
    # inside the array whatever ``group_sizes`` claims
    tile = jnp.clip(tile, 0, tiles_m - 1)
    return tuple(a.astype(i32)
                 for a in (offsets, next_live, group, tile, n_visits))


def weight_copies(col, group, first_unit, next_live, tiles_n: int,
                  buffers: int):
    """The copies of weight tiles that the first visit of ``group`` in
    column sweep ``col`` starts: ``buffers`` triples ``(group, column tile,
    starts)``, the ``i``-th for the UNIT (a group with rows x a column
    tile, in the order the grid meets them) ``i`` after this one, which
    lands ``i`` slots after this unit's in the ring. Every first visit
    starts the unit ``buffers - 1`` ahead, into the slot of the unit
    before this one, whose last visit is over; the call's first unit
    (``first_unit``) also starts itself and those between. A unit past the
    last column sweep is not started. Scalar arithmetic over an indexable
    ``next_live``: the kernel calls it on traced scalars, the tests on
    numbers to count the copies of a call."""
    n_groups = next_live.shape[0] - 1
    out = []
    for ahead in range(buffers):
        here = first_unit if ahead < buffers - 1 else True
        out.append((group, col, here & (col < tiles_n)))
        after = next_live[group + 1]
        wraps = after >= n_groups
        group = jnp.where(wraps, next_live[0], after)
        col = col + wraps
    return out


def grouped_matmul(lhs, rhs, group_sizes, *, row_tile: Optional[int] = None,
                   column_tile: Optional[int] = None,
                   buffers: int = _WEIGHT_BUFFERS,
                   interpret: Optional[bool] = None):
    """``jax.lax.ragged_dot(lhs, rhs, group_sizes)`` through the kernel
    this module describes. ``lhs`` [M, K] sorted by group; ``rhs`` [G, K,
    N]; ``group_sizes`` [G] integers whose sum is at most ``M``. Returns
    ``[M, N]`` in the operands' dtype; the rows past ``sum(group_sizes)``
    are unspecified (not zeros). The tiles follow the static shapes
    (``row_tile``, ``column_tile`` and ``buffers`` are for the tests);
    ``interpret`` defaults to the module switch
    ``flash_attention.INTERPRET``."""
    if interpret is None:
        interpret = _default_interpret()
    m, k = lhs.shape
    if rhs.ndim != 3 or rhs.shape[1] != k \
            or group_sizes.shape != (rhs.shape[0],):
        raise ValueError(
            f"lhs {lhs.shape}, rhs {rhs.shape} and group_sizes "
            f"{group_sizes.shape} are not [M, K], [G, K, N] and [G]")
    if lhs.dtype != rhs.dtype:
        raise ValueError(f"lhs is {lhs.dtype} and rhs {rhs.dtype}")
    n = rhs.shape[2]
    tm = row_tile or _ROW_TILE
    tn = column_tile or _column_tile(k, n, rhs.dtype.itemsize)
    if n % tn:
        raise ValueError(f"column tile {tn} does not divide {n} columns")
    if buffers < 2:
        raise ValueError("a ring of weight tiles has two slots or more")
    return _grouped_matmul_call(lhs, rhs, group_sizes, tm=int(min(tm, m)),
                                tn=int(tn), buffers=int(buffers),
                                interpret=bool(interpret))


@functools.partial(jax.jit,
                   static_argnames=("tm", "tn", "buffers", "interpret"))
def _grouped_matmul_call(lhs, rhs, group_sizes, *, tm, tn, buffers,
                         interpret):
    """Jitted so that an engine program, which calls it twice a layer with
    two shapes, traces and lowers the kernel once a shape."""
    f32, i32 = jnp.float32, jnp.int32
    m, k = lhs.shape
    _, _, n = rhs.shape
    tiles_n = n // tn
    plan = plan_visits(group_sizes, m, tm)
    n_steps = plan[2].shape[0]

    def kernel(off_ref, next_ref, grp_ref, tile_ref, cnt_ref, lhs_ref,
               w_hbm, out_ref, w_buf, sems, slot_ref):
        col, s = pl.program_id(0), pl.program_id(1)
        visits = s < cnt_ref[0]
        g = grp_ref[s]

        def copy(group, sweep, slot):
            """A tile of weights on its way into a slot of the ring."""
            src = w_hbm.at[group, :,
                           pl.ds(pl.multiple_of(sweep * tn, tn), tn)]
            return pltpu.make_async_copy(src, w_buf.at[slot], sems.at[slot])

        @pl.when(visits & ((s == 0) | (g != grp_ref[jnp.maximum(s - 1, 0)])))
        def _next_group():
            first_unit = (col == 0) & (s == 0)
            slot = jnp.where(first_unit, 0,
                             jax.lax.rem(slot_ref[0] + 1, buffers))
            slot_ref[0] = slot
            for ahead, (group, sweep, starts) in enumerate(weight_copies(
                    col, g, first_unit, next_ref, tiles_n, buffers)):
                @pl.when(starts)
                def _():
                    copy(group, sweep,
                         jax.lax.rem(slot + ahead, buffers)).start()
            copy(g, col, slot).wait()

        @pl.when(visits)
        def _visit():
            acc = jnp.dot(lhs_ref[...], w_buf[slot_ref[0]],
                          preferred_element_type=f32)
            row = tile_ref[s] * tm + jax.lax.broadcasted_iota(
                i32, acc.shape, 0)
            ours = (row >= off_ref[g]) & (row < off_ref[g + 1])
            out_ref[...] = jnp.where(
                ours, acc, out_ref[...].astype(f32)).astype(out_ref.dtype)

    itemsize = rhs.dtype.itemsize
    # the ring, the two row tiles each of lhs and the output that the
    # pipeline holds, a visit's float32 product and its select
    vmem = (buffers * k * tn + 2 * tm * k + 2 * tm * tn) * itemsize \
        + 3 * tm * tn * 4
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(tiles_n, n_steps),
            in_specs=[pl.BlockSpec((tm, k),
                                   lambda c, s, *p: (p[3][s], 0)),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((tm, tn),
                                   lambda c, s, *p: (p[3][s], c)),
            scratch_shapes=[pltpu.VMEM((buffers, k, tn), rhs.dtype),
                            pltpu.SemaphoreType.DMA((buffers,)),
                            pltpu.SMEM((1,), i32)]),   # this unit's slot
        out_shape=jax.ShapeDtypeStruct((m, n), lhs.dtype),
        # sequential: the ring and its semaphores carry a tile from the
        # step that starts its copy to the steps that multiply by it
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=int(vmem + (8 << 20))),
        interpret=interpret,
        name="grouped_matmul",
    )(*plan, lhs, rhs)
