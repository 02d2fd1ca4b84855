"""State-space duality (Mamba-2) operators over ragged rows.

The recurrence, a head at a time (``x_t`` in R^P, ``B_t``, ``C_t`` in R^N,
``a_t = dt_t * A`` with ``A < 0``)::

    S_t = exp(a_t) S_{t-1} + dt_t x_t B_t^T          (P x N)
    y_t = S_t C_t + D x_t

(Dao & Gu 2024, "Transformers are SSMs", arXiv:2405.21060). Two forms:

- :func:`ssd_step`: one token a row, the decode tick, in plain
  ``jax.numpy``: the path off the TPU and the oracle of
  :func:`ssd_step_kernel`, the same step as a Pallas kernel over a layer's
  whole state array, in place, that moves the live rows' tiles only (what
  an engine's programs run on a TPU).
- :func:`ssd_chunked`: a PACKED run of ``T`` rows that holds up to ``G``
  sequences, each contiguous and in order (``tok_seg[t]`` = the local index
  of row ``t``'s sequence, ``G`` for a padded row). Every sequence enters
  from its own carried state and leaves its final state. The run is walked
  in blocks of ``chunk`` rows; inside a block the quadratic (attention-like)
  form is masked across sequences, between blocks the ``G`` states carry.
  Plain ``jax.numpy`` over the sequences' GATHERED states
  (:func:`ssd_chunk_gathered` gathers and scatters them): the path off the
  TPU, the whole-sequence forward's, and the oracle of
  :func:`ssd_chunk_kernel`, the same scan as a Pallas kernel over a layer's
  whole state array, in place, that moves the tiles of the sequences
  present in the run only (what an engine's programs run on a TPU).

:func:`causal_conv_chunk` / :func:`causal_conv_step` are the depthwise causal
convolution that precedes the scan, with the carried ``d_conv - 1`` position
tail of each sequence.

The decays, their cumulative sums and the state are float32 whatever the
activations are (a recurrence rounds at every token), and on the TPU every
float32 product of either chunked form asks for ``Precision.HIGHEST``.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import _default_interpret

_HI = jax.lax.Precision.HIGHEST
# one state tile of the step kernel in VMEM (the pipeline holds four: two
# coming in, two going out)
_STATE_TILE_BYTES = 1 << 20
# what the chunk kernel may hold in VMEM (the compiler's default is 16 MiB
# of a v5e's 128)
_CHUNK_VMEM_BYTES = 48 << 20


def _onehot(tok_seg, n_seg: int):
    """[T, G] membership; a padded row (``tok_seg == G``) is in no
    sequence."""
    return tok_seg[:, None] == jnp.arange(n_seg)[None, :]


def causal_conv_step(x, weight, bias, tail):
    """One token a row. ``x`` [B, C]; ``weight`` [K, C] (``weight[K-1]``
    multiplies the current token); ``bias`` [C]; ``tail`` [B, K-1, C] the
    last ``K-1`` inputs, oldest first. Returns ``(y [B, C] float32,
    new_tail)``."""
    window = jnp.concatenate([tail, x[:, None].astype(tail.dtype)], axis=1)
    y = jnp.einsum("bkc,kc->bc", window.astype(jnp.float32),
                   weight.astype(jnp.float32)) + bias.astype(jnp.float32)
    return y, window[:, 1:]


def causal_conv_chunk(x, weight, bias, tail, tok_seg):
    """A packed run. ``x`` [T, C]; ``tail`` [G, K-1, C] the carried tails of
    the run's ``G`` sequences (zeros for one that starts here); ``tok_seg``
    [T] in ``0..G``. Returns ``(y [T, C] float32, new_tail [G, K-1, C])``:
    a sequence with no row in the run keeps its tail."""
    t, _ = x.shape
    g, km1, _ = tail.shape
    oh = _onehot(tok_seg, g)
    idx = jnp.arange(t)
    start = jnp.min(jnp.where(oh, idx[:, None], t), axis=0)        # [G]
    length = jnp.sum(oh, axis=0)                                   # [G]
    # a padded row reads sequence "G": offset 0, an all-zero tail
    start_p = jnp.concatenate([start, jnp.zeros((1,), start.dtype)])
    tail_p = jnp.concatenate([tail, jnp.zeros((1,) + tail.shape[1:],
                                              tail.dtype)])
    off = idx - start_p[tok_seg]                                   # [T]
    xf = x.astype(jnp.float32)
    wf = weight.astype(jnp.float32)
    acc = xf * wf[km1]
    for j in range(1, km1 + 1):
        in_run = jnp.concatenate(
            [jnp.zeros((j, xf.shape[1]), xf.dtype), xf[:-j]])[:t]
        carried = tail_p[tok_seg, jnp.clip(km1 + off - j, 0, km1 - 1)]
        prev = jnp.where((off >= j)[:, None], in_run,
                         carried.astype(jnp.float32))
        acc = acc + prev * wf[km1 - j]
    y = acc + bias.astype(jnp.float32)
    cols = []
    for i in range(km1):
        o = length - km1 + i                                       # [G]
        from_run = x[jnp.clip(start + o, 0, t - 1)]                # [G, C]
        from_old = tail[jnp.arange(g), jnp.clip(length + i, 0, km1 - 1)]
        cols.append(jnp.where((o >= 0)[:, None],
                              from_run.astype(tail.dtype), from_old))
    return y, jnp.stack(cols, axis=1)


def ssd_step(x, dt, A, B, C, D, state):
    """One token a row. ``x`` [R, H, P]; ``dt`` [R, H] (after softplus; 0
    leaves the row's state as it was); ``A``, ``D`` [H]; ``B``, ``C``
    [R, N]; ``state`` [R, H, P, N] float32. Returns ``(y [R, H, P] float32,
    new_state)``."""
    xf = x.astype(jnp.float32)
    dt = dt.astype(jnp.float32)
    decay = jnp.exp(dt * A.astype(jnp.float32))                    # [R, H]
    dbx = (dt[:, :, None] * xf)[..., None] \
        * B.astype(jnp.float32)[:, None, None, :]
    new = decay[:, :, None, None] * state + dbx
    y = jnp.sum(new * C.astype(jnp.float32)[:, None, None, :], axis=-1)
    return y + D.astype(jnp.float32)[None, :, None] * xf, new


def _head_block(n_heads: int, d_head: int, d_state: int) -> int:
    """Heads a grid step of :func:`ssd_step_kernel` moves: as many float32
    ``[d_head, d_state]`` tiles as ``_STATE_TILE_BYTES`` hold. 32 for the
    published 64 x 128."""
    return max(1, min(n_heads, _STATE_TILE_BYTES // (4 * d_head * d_state)))


def ssd_step_kernel(x, dt, A, B, C, D, state, live, first,
                    head_block: Optional[int] = None,
                    interpret: Optional[bool] = None):
    """:func:`ssd_step` as a Pallas kernel over ONE layer's whole state
    array, updated in place. ``x`` [R, H, P]; ``dt`` [R, H]; ``A``, ``D``
    [H]; ``B``, ``C`` [R, N]; ``state`` [S, H, P, N] float32 with
    ``S >= R`` (the engine's ``slots + 1`` rows: row ``i`` of ``x`` steps
    state row ``i``); ``live``, ``first`` [R] bool. Returns ``(y [R, H, P]
    float32, state)``; the returned state IS the argument's buffer
    (``input_output_aliases``) wherever the caller donates it.

    The grid is (row, block of heads). A live row's step brings its
    ``[head_block, P, N]`` tile into VMEM, computes ``S = exp(dt A) S +
    dt x B^T`` and ``y = S C + D x`` in float32 as :func:`ssd_step` does,
    and sends the tile back; a ``first`` row starts from zeros and its old
    tile is never read. A row that is not ``live`` moves NOTHING: its
    steps name the block the pipeline already holds, which is neither
    fetched nor written again, so its state row (and every row past
    ``R``: the scratch row) keeps its bytes and its ``y`` is the skip
    term ``D x`` alone. The bytes a call moves follow its live rows.

    A head's tile lies ``[P, N]`` (``N`` on lanes). What varies with
    ``p`` and not with ``n``, the ``dt x`` of the rank-one update, must
    be spread along lanes, and ``y``'s sum over ``n`` crosses them: two
    cross-lane operations a vector register saturate that unit and hold
    the kernel a fifth under the pipeline's own speed (read on the chip, PR
    27). So ``exp(dt A)`` and ``dt x`` (computed beside the kernel, the
    same float32 products as :func:`ssd_step`'s) come in through SMEM as
    scalars: a decay multiplies its tile as a scalar, eight ``dt x``
    scalars are selected into the sublanes of one register (so ``P`` is
    a multiple of 8), and only ``y``'s sum crosses lanes; it lands in
    lane ``h`` of the row's ``[P, H]`` output block, and ``D x`` is added
    beside the kernel. ``interpret`` defaults to the module switch
    ``flash_attention.INTERPRET``.
    """
    if interpret is None:
        interpret = _default_interpret()
    _, n_heads, d_head = x.shape
    if d_head % 8:
        raise ValueError(f"head size {d_head} is not a multiple of 8")
    if head_block is None:
        head_block = _head_block(n_heads, d_head, state.shape[-1])
    return _ssd_step_call(x, dt, A, B, C, D, state, live, first,
                          head_block=int(min(head_block, n_heads)),
                          interpret=bool(interpret))


def held_tiles(moves, slots: int, nb: int):
    """Where each row's grid steps of a step kernel find their state tile
    (this file's and ``ops/kda.py``'s): ``(row, blk)`` int32 ``[rows]``. A
    row that ``moves`` its tile names its own row and the step's block
    (``blk`` -1); any other names the tile the pipeline holds (the last
    one moved, or before the first the one to come), so nothing is copied
    for it. With nothing to move at all, every step names block 0 of the
    last of the ``slots`` rows and copies it onto itself."""
    rows = moves.shape[0]
    ids = jnp.arange(rows, dtype=jnp.int32)
    before = jax.lax.cummax(jnp.where(moves, ids, -1))
    after = jax.lax.cummin(jnp.where(moves, ids, rows), reverse=True)
    row = jnp.where(before >= 0, before,
                    jnp.where(after < rows, after, slots - 1))
    blk = jnp.where(moves, -1, jnp.where(before >= 0, nb - 1, 0))
    return row.astype(jnp.int32), blk.astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("head_block", "interpret"))
def _ssd_step_call(x, dt, A, B, C, D, state, live, first, *, head_block,
                   interpret):
    """Jitted so that an engine program, which calls it once a state-space
    layer with the same shapes, traces and lowers the kernel once."""
    f32, i32 = jnp.float32, jnp.int32
    rows, n_heads, d_head = x.shape
    slots, _, _, d_state = state.shape
    hb = head_block
    nb = -(-n_heads // hb)
    # heads a loop step holds: independent work hides the lane sum's wait
    unroll = next(u for u in (4, 2, 1) if hb % u == 0)

    live = live.astype(bool)
    reads = live & ~first.astype(bool)
    in_row, in_blk = held_tiles(reads, slots, nb)
    out_row, out_blk = held_tiles(live, slots, nb)
    flags = live.astype(i32) + 2 * reads.astype(i32)
    any_live = jnp.any(live).astype(i32)[None]

    def tile(row_ref, blk_ref):
        def index(r, b, *refs):
            blk = refs[blk_ref][r]
            return refs[row_ref][r], jnp.where(blk < 0, b, blk), 0, 0
        return pl.BlockSpec((1, hb, d_head, d_state), index)

    def kernel(in_row_ref, in_blk_ref, out_row_ref, out_blk_ref, flag_ref,
               any_ref, decay_ref, dx_ref, b_ref, c_ref, s_ref, y_ref,
               o_ref):
        r, b = pl.program_id(0), pl.program_id(1)
        flag = flag_ref[r]

        @pl.when((any_ref[0] == 0) & (r == 0) & (b == 0))
        def _nothing_live():              # the held tile, onto itself
            o_ref[...] = s_ref[...]

        @pl.when(b == 0)
        def _new_row():
            y_ref[...] = jnp.zeros_like(y_ref)

        @pl.when(flag > 0)
        def _step():
            lane = jax.lax.broadcasted_iota(i32, y_ref.shape[1:], 1)
            sublane = jax.lax.broadcasted_iota(i32, (8, d_state), 0)
            b_row, c_row = b_ref[0], c_ref[0]                  # [1, N]
            fresh = flag < 2

            def head(j, y_cols):
                # a ragged last block: its heads past H are padding
                h = jnp.minimum(b * hb + j, n_heads - 1)
                dx = []                   # dt x, [P, N]: p along sublanes
                for at in range(0, d_head, 8):
                    at += h * d_head
                    reg = jnp.full((8, d_state), dx_ref[0, 0, at], f32)
                    for s in range(1, 8):
                        reg = jnp.where(sublane == s, dx_ref[0, 0, at + s],
                                        reg)
                    dx.append(reg)
                old = jnp.where(fresh, 0.0, s_ref[0, j])       # [P, N]
                new = decay_ref[0, 0, h] * old \
                    + jnp.concatenate(dx) * b_row
                o_ref[0, j] = new
                return jnp.where(
                    lane == b * hb + j,
                    jnp.sum(new * c_row, axis=1, keepdims=True), y_cols)

            def heads(i, y_cols):
                for k in range(unroll):
                    y_cols = head(i * unroll + k, y_cols)
                return y_cols

            y_ref[0] += jax.lax.fori_loop(
                0, hb // unroll, heads, jnp.zeros(y_ref.shape[1:], f32))

    def per_row(*block, **kw):
        # a live row's own operands; for any other row those already held
        def index(r, b, *refs):
            return jnp.minimum(refs[2][r], rows - 1), 0, 0
        return pl.BlockSpec((1,) + block, index, **kw)

    dtf, xf = dt.astype(f32), x.astype(f32)
    y_t, new_state = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=6,
            grid=(rows, nb),
            in_specs=[per_row(1, n_heads, memory_space=pltpu.SMEM),
                      per_row(1, n_heads * d_head,
                              memory_space=pltpu.SMEM),
                      per_row(1, d_state), per_row(1, d_state), tile(0, 1)],
            out_specs=[pl.BlockSpec((1, d_head, n_heads),
                                    lambda r, b, *_: (r, 0, 0)),
                       tile(2, 3)]),
        out_shape=[jax.ShapeDtypeStruct((rows, d_head, n_heads), f32),
                   jax.ShapeDtypeStruct(state.shape, f32)],
        # operand 10 (6 prefetched + 4 small ones) is the state
        input_output_aliases={10: 1},
        # sequential: a tile stays in VMEM across the steps that name it
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name="ssd_step",
    )(in_row, in_blk, out_row, out_blk, flags, any_live,
      jnp.exp(dtf * A.astype(f32))[:, None, :],
      (dtf[:, :, None] * xf).reshape(rows, 1, n_heads * d_head),
      B.astype(f32)[:, None, :], C.astype(f32)[:, None, :], state)
    return (y_t.transpose(0, 2, 1) + D.astype(f32)[None, :, None] * xf,
            new_state)


def _chunk_head_block(n_heads: int) -> int:
    """Heads a grid step of :func:`ssd_chunk_kernel` holds: the largest
    divisor of ``n_heads`` up to 16 (what is kept a head block in VMEM,
    chiefly the chunk's ``dt x`` and the lane-padded decay columns, grows
    with it; 16 heads of the published 64 x 128 are a 512 KB state tile)."""
    return next(hb for hb in range(min(16, n_heads), 0, -1)
                if n_heads % hb == 0)


def ssd_chunk_kernel(x, dt, A, B, C, D, state, tok_seg, seg_rows, fresh,
                     chunk: int = 256, head_block: Optional[int] = None,
                     interpret: Optional[bool] = None):
    """:func:`ssd_chunked` as a Pallas kernel over ONE layer's whole state
    array, updated in place. ``x`` [T, H, P]; ``dt`` [T, H]; ``A``, ``D``
    [H]; ``B``, ``C`` [T, N]; ``state`` [S, H, P, N] float32 (the engine's
    ``slots + 1`` rows); ``tok_seg`` [T] in ``0..G`` as in
    :func:`ssd_chunked`; ``seg_rows`` [G] the state row of each of the
    run's sequences; ``fresh`` [G] bool, a sequence that starts here (from
    zeros, whatever its row holds). Returns ``(y [T, H, P] float32,
    state)``; the returned state IS the argument's buffer
    (``input_output_aliases``) wherever the caller donates it.

    The grid is (block of heads, sequence), the sequence innermost. The
    first step of a head block computes, in VMEM and for every row of the
    run at once, what ``_ssd_block`` computes inside a block: the
    cumulative log-decays ``cs`` (a product with the same-sequence causal
    mask, as there), ``C B^T`` under that mask, and a head at a time
    ``exp(cs_t - cs_s)`` times it times ``dt x``. Then each sequence WITH
    rows in the run brings its ``[head_block, P, N]`` state tile in, adds
    ``exp(cs_t) C_t . S`` to its own rows, and sends back the state it
    leaves, ``exp(total) S + sum_s exp(total - cs_s) dt_s x_s B_s^T``. A
    ``fresh`` sequence starts from zeros and its old tile is never read. A
    sequence WITHOUT rows moves nothing: its steps name the tile the
    pipeline already holds, which is neither fetched nor written again, so
    its state row (and the scratch row) keeps its bytes. Nothing of shape
    ``[T, T, H]`` exists: the decay matrix of one head lives in VMEM and
    dies there. The bytes a call moves follow the sequences present.

    Everything per head lies with the run's rows along LANES (``x`` and
    ``y`` cross the kernel's boundary transposed, ``[H, P, T]``): a head is
    then an index into a leading axis, which a loop may take dynamically,
    and a head's ``[P, T]`` output tile is stored whole. ``cs`` is needed
    both along lanes (``cs_t``) and along sublanes (``cs_s``, kept a head a
    ``[T, 1]`` column in scratch). The decays, their sums and the state
    are float32 and every product asks the MXU for float32 accuracy
    (``Precision.HIGHEST``), as :func:`ssd_chunked`'s do. ``T`` above
    ``chunk`` is walked ``chunk`` rows a call. ``interpret`` defaults to
    the module switch ``flash_attention.INTERPRET``.
    """
    if interpret is None:
        interpret = _default_interpret()
    t, n_heads, d_head = x.shape
    if d_head % 8:
        raise ValueError(f"head size {d_head} is not a multiple of 8")
    if head_block is None:
        head_block = _chunk_head_block(n_heads)
    if n_heads % head_block:
        raise ValueError(f"{n_heads} heads do not split into blocks of "
                         f"{head_block}")
    fresh = fresh.astype(bool)
    ys = []
    for lo in range(0, t, chunk):
        hi = min(lo + chunk, t)
        seg = tok_seg[lo:hi]
        # a sequence is fresh in the call that holds its first row
        starts = fresh if not lo else fresh & ~jnp.any(
            _onehot(tok_seg[:lo], seg_rows.shape[0]), axis=0)
        y, state = _ssd_chunk_call(
            x[lo:hi], dt[lo:hi], A, B[lo:hi], C[lo:hi], state, seg,
            seg_rows, starts, head_block=int(head_block),
            interpret=bool(interpret))
        ys.append(y)
    y = ys[0] if len(ys) == 1 else jnp.concatenate(ys)
    f32 = jnp.float32
    return y + D.astype(f32)[None, :, None] * x.astype(f32), state


@functools.partial(jax.jit, static_argnames=("head_block", "interpret"))
def _ssd_chunk_call(x, dt, A, B, C, state, tok_seg, seg_rows, fresh, *,
                    head_block, interpret):
    """One block of rows; jitted so that an engine program, which calls it
    once a state-space layer with the same shapes, traces and lowers the
    kernel once. Returns ``y`` without the skip term."""
    f32, i32 = jnp.float32, jnp.int32
    t, n_heads, d_head = x.shape
    slots, _, _, d_state = state.shape
    n_seg = seg_rows.shape[0]
    hb = head_block
    nb = n_heads // hb
    # lanes a step of the causal walk covers: row tile k needs the rows up
    # to its own end only
    tb = 128 if t % 128 == 0 else t
    hi_dot = functools.partial(jax.lax.dot_general, precision=_HI,
                               preferred_element_type=f32)
    nn = (((1,), (0,)), ((), ()))
    nt = (((1,), (1,)), ((), ()))

    oh = _onehot(tok_seg, n_seg)
    count = jnp.sum(oh, axis=0).astype(i32)
    start = jnp.argmax(oh, axis=0).astype(i32)
    present = count > 0
    reads = present & ~fresh
    flags = present.astype(i32) + 2 * reads.astype(i32)
    any_present = jnp.any(present).astype(i32)[None]

    # where each grid step's state tile lies, step k = block * G + sequence.
    # A sequence that moves its tile names its own row and the step's
    # block; any other step names the tile the pipeline holds (the last one
    # moved, or before the first the one to come), so nothing is copied for
    # it. With nothing to move at all, every step names block 0 of the last
    # row (the scratch row) and copies it onto itself.
    steps = nb * n_seg
    ids = jnp.arange(steps, dtype=i32)
    seq_of, blk_of = ids % n_seg, ids // n_seg

    def held(moves):
        moves = moves[seq_of]
        before = jax.lax.cummax(jnp.where(moves, ids, -1))
        after = jax.lax.cummin(jnp.where(moves, ids, steps), reverse=True)
        src = jnp.where(before >= 0, before, jnp.minimum(after, steps - 1))
        some = (before >= 0) | (after < steps)
        row = jnp.where(some, seg_rows.astype(i32)[seq_of[src]], slots - 1)
        return row.astype(i32), jnp.where(some, blk_of[src], 0).astype(i32)

    in_row, in_blk = held(reads)
    out_row, out_blk = held(present)

    def tile(row_ref, blk_ref):
        def index(b, g, *refs):
            k = b * n_seg + g
            return refs[row_ref][k], refs[blk_ref][k], 0, 0
        return pl.BlockSpec((1, hb, d_head, d_state), index)

    def kernel(in_row_ref, in_blk_ref, out_row_ref, out_blk_ref, start_ref,
               count_ref, flag_ref, any_ref, segr_ref, segc_ref, at_ref,
               acol_ref, dtt_ref, xt_ref, b_ref, c_ref, s_ref, yt_ref,
               o_ref, m_ref, cb_ref, cs_ref, col_ref, dx_ref, lane_ref,
               tot_ref):
        b, g = pl.program_id(0), pl.program_id(1)
        flag = flag_ref[g]
        some = any_ref[0] > 0

        @pl.when(jnp.logical_not(some) & (b == 0) & (g == 0))
        def _nothing_present():           # the held tile, onto itself
            o_ref[...] = s_ref[...]

        @pl.when(jnp.logical_not(some) & (g == 0))
        def _no_rows():
            yt_ref[...] = jnp.zeros_like(yt_ref)

        @pl.when(some & (g == 0))
        def _inside_the_block():
            @pl.when(b == 0)
            def _masks():                 # the same for every head
                segr, segc = segr_ref[...], segc_ref[...]
                same = (segc == segr) & (segc < n_seg)
                sub = jax.lax.broadcasted_iota(i32, (t, t), 0)
                lane = jax.lax.broadcasted_iota(i32, (t, t), 1)
                # m_ref[0][s, t]: row t sees row s; m_ref[1] its transpose
                m_ref[0] = (same & (sub <= lane)).astype(f32)
                m_ref[1] = (same & (sub >= lane)).astype(f32)
                cb_ref[...] = hi_dot(b_ref[...], c_ref[...], nt) * m_ref[0]

            # cumulative log-decay since the row's sequence entered the
            # block, along lanes (cs_t) and as columns (cs_s)
            cs = hi_dot(at_ref[...], m_ref[0], nn)               # [hb, T]
            cs_col = hi_dot(m_ref[1], acol_ref[0], nn)           # [T, hb]
            cs_ref[...] = cs
            for j in range(hb):           # a head: a leading index
                lane_ref[0, j] = cs[j:j + 1, :]
                col_ref[j] = cs_col[:, j:j + 1]

            def head(j, carry):
                dx = xt_ref[j].astype(f32) * dtt_ref[j]
                dx_ref[j] = dx                                   # [P, T]
                for lo in range(0, t, tb):
                    hi = lo + tb
                    # (C_t . B_s) exp(cs_t - cs_s) for s <= t, [s, t]
                    gap = lane_ref[0, j, :, lo:hi] - col_ref[j, :hi, :]
                    w = cb_ref[:hi, lo:hi] * jnp.exp(
                        gap * m_ref[0, :hi, lo:hi])
                    yt_ref[j, :, lo:hi] = hi_dot(dx[:, :hi], w, nn)
                return carry

            jax.lax.fori_loop(0, hb, head, 0)

        @pl.when(flag > 0)
        def _sequence():
            lane = jax.lax.broadcasted_iota(i32, (1, t), 1)
            first = start_ref[g]
            inside = (lane >= first) & (lane < first + count_ref[g])
            cs = cs_ref[...]
            total = jnp.sum(jnp.where(inside, at_ref[...], 0.0), axis=1,
                            keepdims=True)                       # [hb, 1]
            # exp(cs_t) into the rows' outputs, exp(total - cs_s) into the
            # state the sequence leaves
            into = jnp.where(inside, jnp.exp(cs), 0.0)
            left = jnp.where(inside, jnp.exp(total - cs), 0.0)
            held = jnp.broadcast_to(jnp.exp(total), (hb, d_state))
            for j in range(hb):
                lane_ref[1, j] = into[j:j + 1, :]
                lane_ref[2, j] = left[j:j + 1, :]
                tot_ref[j] = held[j:j + 1, :]
            carried = flag >= 2

            def head(j, carry):
                old = jnp.where(carried, s_ref[0, j], 0.0)       # [P, N]
                yt_ref[j] += lane_ref[1, j] * hi_dot(old, c_ref[...], nt)
                o_ref[0, j] = tot_ref[j] * old + hi_dot(
                    dx_ref[j] * lane_ref[2, j], b_ref[...], nn)
                return carry

            jax.lax.fori_loop(0, hb, head, 0)

    def whole(*shape):
        return pl.BlockSpec(shape, lambda b, g, *_: (0,) * len(shape))

    def per_block(*shape):
        return pl.BlockSpec(
            shape, lambda b, g, *_: (b,) + (0,) * (len(shape) - 1))

    live = tok_seg < n_seg                 # a padded row moves nothing
    dtf = jnp.where(live[:, None], dt.astype(f32), 0.0)
    a = dtf * A.astype(f32)[None, :]                             # [T, H]
    seg = tok_seg.astype(i32)
    y_t, new_state = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=8,
            grid=(nb, n_seg),
            in_specs=[whole(1, t), whole(t, 1), per_block(hb, t),
                      per_block(1, t, hb), per_block(hb, 1, t),
                      per_block(hb, d_head, t), whole(t, d_state),
                      whole(t, d_state), tile(0, 1)],
            out_specs=[per_block(hb, d_head, t), tile(2, 3)],
            scratch_shapes=[pltpu.VMEM((2, t, t), f32),
                            pltpu.VMEM((t, t), f32),
                            pltpu.VMEM((hb, t), f32),
                            pltpu.VMEM((hb, t, 1), f32),
                            pltpu.VMEM((hb, d_head, t), f32),
                            pltpu.VMEM((3, hb, 1, t), f32),
                            pltpu.VMEM((hb, 1, d_state), f32)]),
        out_shape=[jax.ShapeDtypeStruct((n_heads, d_head, t), f32),
                   jax.ShapeDtypeStruct(state.shape, f32)],
        # operand 16 (8 prefetched + 8 small ones) is the state
        input_output_aliases={16: 1},
        # sequential: a tile stays in VMEM across the steps that name it
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_CHUNK_VMEM_BYTES),
        interpret=interpret,
        name="ssd_chunk",
    )(in_row, in_blk, out_row, out_blk, start, count, flags, any_present,
      seg[None, :], seg[:, None], a.T,
      a.reshape(t, nb, hb).transpose(1, 0, 2), dtf.T[:, None, :],
      x.transpose(1, 2, 0), B.astype(f32), C.astype(f32), state)
    return y_t.transpose(2, 0, 1), new_state


def _ssd_block(x, dt, A, B, C, oh, state):
    """One block of ``Q`` rows against the ``G`` carried states."""
    f32 = jnp.float32
    a = dt * A[None, :]                                            # [Q, H]
    ohf = oh.astype(f32)
    same = jnp.einsum("tg,sg->ts", ohf, ohf) > 0                   # [Q, Q]
    q = x.shape[0]
    causal = jnp.tril(jnp.ones((q, q), bool))
    m = (same & causal).astype(f32)
    # cumulative log-decay since the row's sequence entered the block
    cs = jnp.matmul(m, a, precision=_HI)                           # [Q, H]
    total = jnp.matmul(ohf.T, a, precision=_HI)                    # [G, H]
    # inside the block: (C_t . B_s) exp(cs_t - cs_s) dt_s x_s, s <= t
    cb = jnp.matmul(C, B.T, precision=_HI) * m                     # [Q, Q]
    gap = cs[:, None, :] - cs[None, :, :]                          # [Q, Q, H]
    w = cb[:, :, None] * jnp.exp(jnp.where(m[:, :, None] > 0, gap, 0.0))
    dx = dt[:, :, None] * x                                        # [Q, H, P]
    y = jnp.einsum("tsh,shp->thp", w, dx, precision=_HI)
    # from the carried state: exp(cs_t) C_t . S[seq(t)]
    cg = ohf[:, :, None] * C[:, None, :]                           # [Q, G, N]
    y = y + jnp.exp(cs)[:, :, None] * jnp.einsum(
        "tgn,ghpn->thp", cg, state, precision=_HI)
    # the states the block leaves
    seq_total = jnp.matmul(ohf, total, precision=_HI)              # [Q, H]
    left = jnp.exp(seq_total - cs)[:, :, None] * dx                # [Q, H, P]
    bg = ohf[:, :, None] * B[:, None, :]                           # [Q, G, N]
    new = jnp.exp(total)[:, :, None, None] * state + jnp.einsum(
        "shp,sgn->ghpn", left, bg, precision=_HI)
    return y, new


def ssd_chunked(x, dt, A, B, C, D, state, tok_seg, chunk: int = 256):
    """A packed run of ``T`` rows over ``G`` sequences. ``x`` [T, H, P];
    ``dt`` [T, H]; ``A``, ``D`` [H]; ``B``, ``C`` [T, N]; ``state``
    [G, H, P, N] float32, each sequence's carried state (zeros for one that
    starts here); ``tok_seg`` [T] in ``0..G``, a sequence's rows contiguous
    and in order. Returns ``(y [T, H, P] float32, final [G, H, P, N])``; a
    sequence with no row keeps its state; a padded row's ``y`` means
    nothing."""
    f32 = jnp.float32
    t = x.shape[0]
    g = state.shape[0]
    xf, dtf = x.astype(f32), dt.astype(f32)
    Af, Bf, Cf = A.astype(f32), B.astype(f32), C.astype(f32)
    oh = _onehot(tok_seg, g)
    # a padded row moves nothing
    live = jnp.any(oh, axis=1)
    dtf = jnp.where(live[:, None], dtf, 0.0)
    ys = []
    for lo in range(0, t, chunk):
        hi = min(lo + chunk, t)
        y, state = _ssd_block(xf[lo:hi], dtf[lo:hi], Af, Bf[lo:hi],
                              Cf[lo:hi], oh[lo:hi], state)
        ys.append(y)
    y = ys[0] if len(ys) == 1 else jnp.concatenate(ys)
    return y + D.astype(f32)[None, :, None] * xf, state


def ssd_chunk_gathered(x, dt, A, B, C, D, state, tok_seg, seg_rows, fresh,
                       chunk: int = 256):
    """:func:`ssd_chunked` over ONE layer's whole state array ``state``
    [S, H, P, N], with :func:`ssd_chunk_kernel`'s arguments and results:
    the rows ``seg_rows`` [G] of the run's sequences are gathered, those
    of a ``fresh`` [G] sequence zeroed, scanned and scattered back. What
    an engine's programs run off the TPU, and the kernel's oracle."""
    carried = jnp.where(fresh[:, None, None, None], 0.0, state[seg_rows])
    y, new = ssd_chunked(x, dt, A, B, C, D, carried, tok_seg, chunk)
    return y, state.at[seg_rows].set(new)


def ssd_recurrence(x, dt, A, B, C, D, state):
    """The definition, token by token, for ONE sequence: ``x`` [S, H, P],
    ``state`` [H, P, N]. What the two forms above are tested against."""
    def step(s, inp):
        xt, dtt, bt, ct = inp
        y, s = ssd_step(xt[None], dtt[None], A, bt[None], ct[None], D,
                        s[None])
        return s[0], y[0]

    state, y = jax.lax.scan(step, state.astype(jnp.float32), (x, dt, B, C))
    return y, state
