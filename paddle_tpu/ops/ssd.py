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

:func:`causal_conv_chunk` / :func:`causal_conv_step` are the depthwise causal
convolution that precedes the scan, with the carried ``d_conv - 1`` position
tail of each sequence.

The decays, their cumulative sums and the state are float32 whatever the
activations are (a recurrence rounds at every token). The chunked scan is
plain ``jax.numpy`` (no Pallas kernel yet); on the TPU its float32
products ask for ``Precision.HIGHEST``.
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

    # where each grid step's state tile lies. A row that moves its tile
    # names its own row and the step's block; any other step names the
    # tile the pipeline holds (the last one moved, or before the first
    # the one to come), so nothing is copied for it. With nothing to move
    # at all, every step names block 0 of the last row and copies it onto
    # itself.
    ids = jnp.arange(rows, dtype=i32)

    def held(moves):
        before = jax.lax.cummax(jnp.where(moves, ids, -1))
        after = jax.lax.cummin(jnp.where(moves, ids, rows), reverse=True)
        row = jnp.where(before >= 0, before,
                        jnp.where(after < rows, after, slots - 1))
        blk = jnp.where(moves, -1, jnp.where(before >= 0, nb - 1, 0))
        return row.astype(i32), blk.astype(i32)

    live = live.astype(bool)
    reads = live & ~first.astype(bool)
    in_row, in_blk = held(reads)
    out_row, out_blk = held(live)
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
