"""The gated delta rule over ragged rows, with a decay a CHANNEL (Kimi Delta
Attention) or ONE decay a head (the gated delta rule of Yang et al.,
arXiv:2412.06464), chosen by the decay's shape.

The recurrence, a head at a time (``q_t``, ``k_t`` in R^K, L2-normed; ``v_t``
in R^V, and ``V`` need not be ``K``: the state is ``K x V``, 96 x 192 in one
model, 128 x 128 in another; ``a_t`` in (0, 1)^K given as ``log a_t <= 0``;
``b_t`` in (0, 2): a write strength over 1 gives ``I - b k k^T`` a negative
eigenvalue, so a key written twice can flip what the state answers; every
form here holds on the whole range)::

    S_t = (I - b_t k_t k_t^T) Diag(a_t) S_{t-1} + b_t k_t v_t^T      (K x V)
    o_t = S_t^T q_t

(Kimi Linear, arXiv:2510.26692; the delta rule of Yang et al.,
arXiv:2406.06484, with Mamba-2's scalar decay made a vector). ``q`` arrives
already scaled. The erase term ``b k k^T`` is what ``ops/ssd.py`` does not
have: a chunk of rows is a triangular solve where Mamba-2's is a product.

``log_a`` of ``[.., H, K]`` is a decay a channel; ``[.., H, 1]`` is ONE
decay a head, the same in every channel. The step takes the broadcast as it
is. The chunk form does not: with a scalar decay the factor leaves the
inner product, ``L[t, i] = b_t e^{g_t - g_i} <k_t, k_i>``, so the pair
products are two plain ``[T, K] x [K, T]`` products a head times a ``[H, T,
T]`` array of exponentials of differences (:func:`_scalar_decayed_products`:
no ``[16, 16, H, K]`` operand a block, no second product through a
reference row). Everything after the pair products (the solve, the inverse,
the state products, the pieces, the padded rows) is the same code for both.

Three forms in plain ``jax.numpy``, and the step once more as a kernel:

- :func:`kda_recurrence`: the definition, a ``lax.scan`` over the tokens of
  ONE sequence. The oracle of the other two; never on the engine's path.
- :func:`kda_step`: one token a row, the decode tick off the TPU, and the
  oracle of :func:`kda_step_kernel`: the same step as a Pallas kernel over
  a layer's whole state array, in place, that reads a live row's tile once
  and writes it once (both decays; what ``delta_rule_rows`` takes under
  ``state_impl`` ``"pallas"``: an engine's decode rows on a TPU).
- :func:`kda_chunked`: a PACKED run of ``T`` rows that holds up to ``G``
  sequences, each contiguous and in order (``tok_seg[t]`` = the local index
  of row ``t``'s sequence, ``G`` for a padded row), every sequence entering
  from its own carried state and leaving its final one. With ``G_t`` the
  cumulative sum of ``log a`` and the step written ``S_t = Diag(a_t)
  S_{t-1} + k_t w_t^T``::

      (I + L) W = Diag(b) (V - (K * e^G) S_0),
                       L[t, i] = b_t <k_t * e^{G_t - G_i}, k_i>,  i < t
      O   = (Q * e^G) S_0 + A W,   A[t, i] = <q_t * e^{G_t - G_i}, k_i>, i <= t
      S_C = Diag(e^{G_C}) S_0 + sum_i (e^{G_C - G_i} * k_i) w_i^T

  the same mathematics, no approximation. ``e^{G_t - G_i}`` is always the
  exponential of a DIFFERENCE that is <= 0 and never a quotient of two
  exponentials (a strong decay overflows ``1 / e^{G_i}``): inside a block of
  ``_BLOCK`` rows the difference is formed a pair; between blocks through
  the later block's first row, ``e^{G_t - G_ref} e^{G_ref - G_i}`` with
  both factors <= 1, which makes the rest one product a block. ``(I +
  L)^{-1}`` is built by the block form of forward substitution, doubling
  the block from one row to the whole run: no step walks the tokens. The
  blocks of ``L`` that the doubling reads are cut out by static slices,
  halving ``L``'s diagonal blocks from the top down (no mask, no sum, each
  element below the diagonal moved once).

The decay stays in log space; the decays, their sums, ``L``, ``W`` and the
state are float32 whatever the activations are (a recurrence rounds at every
token), and every float32 product asks for ``Precision.HIGHEST``.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import _default_interpret
from .ssd import _head_block, held_tiles

_HI = jax.lax.Precision.HIGHEST
_F32 = jnp.float32
# rows of a block whose decays are formed a pair, [B, B, K] a head
_BLOCK = 16
# the rows of a piece of a longer run (the engine's ``prefill_chunk`` of 256
# is one piece; the whole-sequence ``forward`` walks a long prompt in these)
_PIECE = 256


def l2norm(x, eps: float = 1e-6):
    """``x / sqrt(sum x^2 + eps)`` over the last axis, float32."""
    x = x.astype(_F32)
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + eps)


def kda_step(q, k, v, log_a, b, state):
    """One token a row. ``q``, ``k`` [R, H, K]; ``log_a`` [R, H, K] or, one
    decay a head, [R, H, 1]; ``v`` [R, H, V];
    ``b`` [R, H]; ``state`` [R, H, K, V] float32. A row with ``log_a`` 0
    and ``b`` 0 leaves its state as it was. Returns ``(o [R, H, V] float32,
    new_state)``. One pass over the decayed state gives both ``k^T S`` and
    ``q^T S``; ``o`` follows without reading the new state:
    ``S_t^T q = S'^T q + b (q . k) (v - S'^T k)`` with ``S' = Diag(a)
    S_{t-1}``."""
    q, k, v = q.astype(_F32), k.astype(_F32), v.astype(_F32)
    b = b.astype(_F32)[..., None]
    decayed = jnp.exp(log_a.astype(_F32))[..., None] * state
    seen = jnp.einsum("xrhk,rhkv->xrhv", jnp.stack([k, q]), decayed,
                      precision=_HI)
    w = b * (v - seen[0])                                      # [R, H, V]
    new = decayed + k[..., None] * w[..., None, :]
    o = seen[1] + jnp.sum(q * k, axis=-1, keepdims=True) * w
    return o, new


def kda_step_kernel(q, k, v, log_a, b, state, live, first,
                    head_block: Optional[int] = None,
                    interpret: Optional[bool] = None):
    """:func:`kda_step` as a Pallas kernel over ONE layer's whole state
    array, updated in place. ``q``, ``k`` [R, H, K]; ``log_a`` [R, H, K]
    or, one decay a head, [R, H, 1]; ``v`` [R, H, V]; ``b`` [R, H];
    ``state`` [S, H, K, V] float32 with ``S > R`` (the engine's ``slots +
    1`` rows: row ``i`` of the inputs steps state row ``i``); ``live``,
    ``first`` [R] bool. Returns ``(o [R, H, V] float32, state)``; the
    returned state IS the argument's buffer (``input_output_aliases``)
    wherever the caller donates it.

    The grid is (row, block of heads), as ``ops/ssd.py ssd_step_kernel``'s,
    with its tile size (``ssd._head_block``: 16 heads of 128 x 128, 10 of
    96 x 256) and its walk of the tiles (``ssd.held_tiles``). A live row's
    step brings its ``[head_block, K, V]`` tile into VMEM ONCE and, a head
    at a time and all in float32 on the vector unit (a multiply and an add
    an element: no product is rounded to bfloat16), forms ``S' = Diag(a)
    S``, ``k^T S'`` and ``q^T S'`` from that one pass, ``w = b (v - k^T
    S')``, ``S' + k w^T`` (sent back, once) and ``o = q^T S' + (q . k) w``
    as :func:`kda_step` writes them. A ``first`` row starts from zeros and
    its old tile is never read. A row that is not ``live`` moves NOTHING:
    its steps name the block the pipeline already holds, so its state row
    (and every row past ``R``: the scratch row) keeps its bytes, and its
    ``o`` is zeros.

    A head's tile lies ``[K, V]`` (``V`` on lanes), so ``v``, ``w`` and
    ``o`` are rows as they arrive and the sums over ``K`` add registers
    (one sublane reduction a head and sum). ``k``, ``q`` and a decay a
    channel run along ``K``, the sublanes: a (row, block)'s operands cross
    as one ``[channels x heads, K]`` block, are turned once (``K`` a
    multiple of 8: whole sublane groups) and a head's column is spread
    along the lanes by a lane broadcast. The finding in
    ``ssd_step_kernel``'s docstring did NOT carry over: the same columns
    built from SMEM scalars, eight selects a register, were bound by that
    arithmetic at 0.83 / 1.06 ms a call where this form is bound by the
    tiles' copies at 0.64 / 0.36 (read on the chip, PR 48). ``b``, ``q .
    k`` and ONE decay a head are SMEM scalars; ``e^{log a}`` and ``q . k``
    are computed beside the kernel, the same float32 operations as
    :func:`kda_step`'s. ONE decay a head (``log_a`` [R, H, 1]) multiplies
    its tile as a scalar; a decay a channel as a third column: one kernel,
    the form chosen by the decay's shape. ``interpret`` defaults to the
    module switch ``flash_attention.INTERPRET``.
    """
    if interpret is None:
        interpret = _default_interpret()
    _, n_heads, d_k = k.shape
    if d_k % 8:
        raise ValueError(f"key size {d_k} is not a multiple of 8")
    if log_a.shape[-1] not in (1, d_k):
        raise ValueError(f"a decay of {log_a.shape[-1]} channels beside "
                         f"keys of {d_k}")
    if head_block is None:
        head_block = _head_block(n_heads, d_k, state.shape[-1])
    return _kda_step_call(q, k, v, log_a, b, state, live, first,
                          head_block=int(min(head_block, n_heads)),
                          interpret=bool(interpret))


@functools.partial(jax.jit, static_argnames=("head_block", "interpret"))
def _kda_step_call(q, k, v, log_a, b, state, live, first, *, head_block,
                   interpret):
    """Jitted so that an engine program, which calls it once a delta-rule
    layer with the same shapes, traces and lowers the kernel once."""
    f32, i32 = _F32, jnp.int32
    rows, n_heads, d_k = k.shape
    slots, _, _, d_v = state.shape
    hb = head_block
    nb = -(-n_heads // hb)
    channel = log_a.shape[-1] != 1

    live = live.astype(bool)
    reads = live & ~first.astype(bool)
    in_row, in_blk = held_tiles(reads, slots, nb)
    out_row, out_blk = held_tiles(live, slots, nb)
    flags = live.astype(i32) + 2 * reads.astype(i32)
    any_live = jnp.any(live).astype(i32)[None]

    def blocks(x):
        """[R, H, ...] -> [R * nb, 1, hb, ...], the heads past H zeros."""
        x = jnp.pad(x.astype(f32), ((0, 0), (0, nb * hb - n_heads))
                    + ((0, 0),) * (x.ndim - 2))
        return x.reshape((rows * nb, 1, hb) + x.shape[2:])

    # a (row, block)'s operands. A scalar a head through SMEM: b, q . k
    # and ONE decay. What runs along K as they arrive, [channels * hb, K]
    # (whole lanes; the kernel turns the block into columns): k, q and a
    # decay a channel.
    qf, kf = q.astype(f32), k.astype(f32)
    decay = jnp.exp(log_a.astype(f32))
    by_head = [b, jnp.sum(qf * kf, axis=-1)] \
        + ([] if channel else [decay[..., 0]])
    scalars = jnp.concatenate([blocks(x) for x in by_head], axis=-1)
    by_channel = [kf, qf] + ([decay] if channel else [])
    along_k = jnp.concatenate([blocks(x) for x in by_channel], axis=2)

    def tile(row_ref, blk_ref):
        def index(r, b, *refs):
            blk = refs[blk_ref][r]
            return refs[row_ref][r], jnp.where(blk < 0, b, blk), 0, 0
        return pl.BlockSpec((1, hb, d_k, d_v), index)

    def kernel(in_row_ref, in_blk_ref, out_row_ref, out_blk_ref, flag_ref,
               any_ref, sc_ref, v_ref, c_ref, s_ref, y_ref, o_ref):
        r, blk = pl.program_id(0), pl.program_id(1)
        flag = flag_ref[r]

        @pl.when((any_ref[0] == 0) & (r == 0) & (blk == 0))
        def _nothing_live():              # the held tile, onto itself
            o_ref[...] = s_ref[...]

        @pl.when(flag == 0)
        def _not_live():
            y_ref[...] = jnp.zeros_like(y_ref)

        @pl.when(flag > 0)
        def _step():
            fresh = flag < 2
            cols = c_ref[0, 0].T          # [K, channels * hb]: K on sublanes

            def column(c, j):
                """[K, V]: channel operand ``c`` of head ``j`` down the
                sublanes, spread along the lanes."""
                at = c * hb + j
                return jnp.broadcast_to(cols[:, at:at + 1], (d_k, d_v))

            ys = []
            for j in range(hb):           # static: a column is a lane slice
                k_col = column(0, j)
                old = jnp.where(fresh, 0.0, s_ref[0, j])           # [K, V]
                decayed = (column(2, j) if channel
                           else sc_ref[0, 0, 2 * hb + j]) * old
                k_s = jnp.sum(k_col * decayed, axis=0, keepdims=True)
                q_s = jnp.sum(column(1, j) * decayed, axis=0, keepdims=True)
                w = sc_ref[0, 0, j] * (v_ref[0, 0, j:j + 1, :] - k_s)
                o_ref[0, j] = decayed + k_col * w
                ys.append(q_s + sc_ref[0, 0, hb + j] * w)          # [1, V]
            y_ref[0, 0] = jnp.concatenate(ys)

    def per_step(*block, **kw):
        # a live row's own operands; for any other row those already held
        def index(r, b, *refs):
            blk = refs[3][r]
            return (jnp.minimum(refs[2][r], rows - 1) * nb
                    + jnp.where(blk < 0, b, blk),) + (0,) * len(block)
        return pl.BlockSpec((1,) + block, index, **kw)

    y, new_state = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=6,
            grid=(rows, nb),
            in_specs=[per_step(1, scalars.shape[-1],
                               memory_space=pltpu.SMEM),
                      per_step(1, hb, d_v),
                      per_step(*along_k.shape[1:]), tile(0, 1)],
            out_specs=[pl.BlockSpec((1, 1, hb, d_v),
                                    lambda r, b, *_: (r * nb + b, 0, 0, 0)),
                       tile(2, 3)]),
        out_shape=[jax.ShapeDtypeStruct((rows * nb, 1, hb, d_v), f32),
                   jax.ShapeDtypeStruct(state.shape, f32)],
        # operand 9 (6 prefetched + 3 small ones) is the state
        input_output_aliases={9: 1},
        # sequential: a tile stays in VMEM across the steps that name it
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name="kda_step",
    )(in_row, in_blk, out_row, out_blk, flags, any_live, scalars,
      blocks(v), along_k, state)
    return y.reshape(rows, nb * hb, d_v)[:, :n_heads], new_state


def kda_recurrence(q, k, v, log_a, b, state):
    """The definition, token by token, for ONE sequence: ``q``, ``k``,
    ``log_a`` [S, H, K], ``v`` [S, H, V], ``b`` [S, H], ``state`` [H, K,
    V]. What the two forms are tested against."""
    def step(s, inp):
        o, s = kda_step(*(x[None] for x in inp), s[None])
        return s[0], o[0]

    state, o = jax.lax.scan(step, state.astype(_F32), (q, k, v, log_a, b))
    return o, state


def _unit_lower_inverse(low):
    """``(I + low)^{-1}`` for ``low`` [..., T, T] strictly lower triangular,
    ``T`` a power of two: block forward substitution, the block doubling.
    The inverse of a diagonal block of 1 is 1; ``[[A, 0], [C, B]]^{-1} =
    [[A^-1, 0], [-B^-1 C A^-1, B^-1]]`` joins every pair of neighbouring
    blocks at once, ``log2 T`` times. The ``C`` of every level are cut out
    of ``low`` beforehand, from the top down and by slices alone: a
    diagonal block of ``2h`` rows gives its lower left ``[h, h]`` to level
    ``h`` and its two diagonal blocks of ``h`` rows to the level below
    (the levels' blocks tile the strict lower triangle once; nothing is
    masked or summed, and each level halves what the next one reads)."""
    t = low.shape[-1]
    lead = low.shape[:-2]
    lower = {}                              # level h: [..., T / 2h, h, h]
    diag, h = low[..., None, :, :], t // 2  # [..., T / 2h, 2h, 2h]
    while h:
        lower[h] = diag[..., h:, :h]
        if h > 1:
            diag = jnp.stack([diag[..., :h, :h], diag[..., h:, h:]],
                             -3).reshape(lead + (t // h, h, h))
        h //= 2
    inv = jnp.ones(lead + (t, 1, 1), _F32)          # [..., blocks, s, s]
    s = 1
    while s < t:
        a, b = inv[..., 0::2, :, :], inv[..., 1::2, :, :]
        under = -jnp.matmul(b, jnp.matmul(lower[s], a, precision=_HI),
                            precision=_HI)
        inv = jnp.concatenate(
            [jnp.concatenate([a, jnp.zeros_like(a)], -1),
             jnp.concatenate([under, b], -1)], -2)
        s *= 2
    return inv[..., 0, :, :]


def _decayed_products(rows, k, g, pair_ok):
    """``out[x, h, t, i] = <rows[x, t, h] * e^{g_t - g_i}, k[i, h]>`` where
    ``pair_ok[t, i]`` (which implies ``i <= t``), else 0. ``rows`` [X, T,
    H, K], ``k``, ``g`` [T, H, K] (``g`` the running sum of ``log a``,
    non-increasing along a sequence). Pairs of one block: the difference a
    pair. Pairs of two blocks: through the later block's first row."""
    x, t, h, kk = rows.shape
    nb = t // _BLOCK
    blk = jnp.arange(t) // _BLOCK
    # -- inside a block -----------------------------------------------------
    rb = rows.reshape(x, nb, _BLOCK, h, kk)
    kb = k.reshape(nb, _BLOCK, h, kk)
    gb = g.reshape(nb, _BLOCK, h, kk)
    gap = gb[:, :, None] - gb[:, None, :]                  # [nb, B, B, H, K]
    near = jnp.einsum(
        "xjbhk,jbihk->xhjbi", rb,
        kb[:, None] * jnp.exp(jnp.minimum(gap, 0.0)), precision=_HI)
    # the blocks on the diagonal of [T, T]
    inside = (near[:, :, :, :, None, :]
              * jnp.eye(nb, dtype=_F32)[:, None, :, None]).reshape(x, h, t, t)
    if nb == 1:
        return jnp.where(pair_ok, inside, 0.0)
    # -- between blocks -----------------------------------------------------
    # g at the row before each block (block 0 has no earlier block)
    ref = jnp.concatenate([jnp.zeros((1, h, kk), _F32),
                           g[_BLOCK - 1:-1:_BLOCK]])              # [nb, H, K]
    left = rb * jnp.exp(jnp.minimum(gb - ref[:, None], 0.0))      # rows' side
    right = k[None] * jnp.exp(jnp.minimum(ref[:, None] - g[None], 0.0))
    far = jnp.einsum("xjbhk,jihk->xhjbi", left, right,
                     precision=_HI).reshape(x, h, t, t)
    earlier = blk[None, :] < blk[:, None]                         # [T, T]
    return jnp.where(pair_ok, jnp.where(earlier, far, inside), 0.0)


def _scalar_decayed_products(rows, k, g, pair_ok):
    """:func:`_decayed_products` for ONE decay a head: ``g`` [T, H, 1], so
    ``out[x, h, t, i] = e^{g_t - g_i} <rows[x, t, h], k[i, h]>`` where
    ``pair_ok[t, i]``, else 0. The exponent is formed a pair of rows, once
    for the whole run (``[H, T, T]``, a difference that is <= 0 wherever the
    pair counts), and multiplies a plain product over the channels."""
    gh = g[..., 0].T                                               # [H, T]
    decay = jnp.exp(jnp.minimum(gh[:, :, None] - gh[:, None, :], 0.0))
    dots = jnp.einsum("xthk,ihk->xhti", rows, k, precision=_HI)
    return jnp.where(pair_ok, dots * decay, 0.0)


def _kda_block(q, k, v, log_a, b, oh, state):
    """One run of ``T`` rows (a power of two, ``_BLOCK`` or more) against the
    ``G`` carried states ``state`` [G, H, K, V]; ``oh`` [T, G] membership."""
    t = q.shape[0]
    ohf = oh.astype(_F32)
    same = jnp.matmul(ohf, ohf.T) > 0                              # [T, T]
    upto = jnp.tril(jnp.ones((t, t), bool))
    g = jnp.cumsum(log_a, axis=0)                                  # [T, H, K]
    # the sum since the row's sequence entered the run, and its total
    first = jnp.argmax(oh, axis=0)                                 # [G]
    g_before = jnp.where((first > 0)[:, None, None],
                         g[jnp.maximum(first - 1, 0)], 0.0)        # [G, H, K]
    last = t - 1 - jnp.argmax(oh[::-1], axis=0)
    total = g[last] - g_before                                     # [G, H, K]
    g_seq = g - jnp.einsum("tg,ghk->thk", ohf, g_before, precision=_HI)
    to_end = jnp.einsum("tg,ghk->thk", ohf, total, precision=_HI) - g_seq

    # one decay a head ([T, H, 1]) leaves the inner product; a decay a
    # channel does not
    pair_products = _scalar_decayed_products \
        if g.shape[-1] == 1 and k.shape[-1] > 1 else _decayed_products
    prods = pair_products(jnp.stack([k, q]), k, g, same & upto)
    a_kk = jnp.where(jnp.eye(t, dtype=bool), 0.0, prods[0])        # i < t
    a_qk = prods[1]                                                # i <= t
    bh = b.T[:, :, None]                                           # [H, T, 1]
    # what the carried states answer to the decayed keys and queries
    # (each row against its own sequence's state: the rows masked a
    # sequence, one product over (sequence, channel); a three-operand
    # einsum would be left to form a [K, V] outer product a row)
    by_seq = ohf.T[:, :, None, None]                               # [G, T, 1, 1]
    grown = jnp.stack([k, q]) * jnp.exp(jnp.minimum(g_seq, 0.0))
    from_state = jnp.einsum("xgthk,ghkv->xhtv", grown[:, None] * by_seq,
                            state, precision=_HI)
    rhs = bh * (v.transpose(1, 0, 2) - from_state[0])              # [H, T, V]
    with jax.named_scope("inverse"):
        inv = _unit_lower_inverse(bh * a_kk)
    w = jnp.matmul(inv, rhs, precision=_HI)
    o = from_state[1] + jnp.matmul(a_qk, w, precision=_HI)         # [H, T, V]
    left = k * jnp.exp(jnp.minimum(to_end, 0.0))                   # [T, H, K]
    new = jnp.exp(total)[..., None] * state + jnp.einsum(
        "gthk,htv->ghkv", left[None] * by_seq, w, precision=_HI)
    present = jnp.any(oh, axis=0)
    return o.transpose(1, 0, 2), jnp.where(
        present[:, None, None, None], new, state)


def _piece_rows(n: int) -> int:
    """The rows of a piece that holds ``n``: a power of two (the inverse
    doubles its blocks), at least a block."""
    return max(_BLOCK, 1 << (n - 1).bit_length())


def kda_chunked(q, k, v, log_a, b, state, tok_seg):
    """A packed run of ``T`` rows over ``G`` sequences. ``q``, ``k`` [T, H,
    K]; ``log_a`` [T, H, K] or, one decay a head, [T, H, 1]; ``v`` [T, H,
    V]; ``b`` [T, H]; ``state`` [G, H, K,
    V] float32, each sequence's carried state (zeros for one that starts
    here); ``tok_seg`` [T] in ``0..G``, a sequence's rows contiguous and in
    order. Returns ``(o [T, H, V] float32, final [G, H, K, V])``; a sequence
    with no row keeps its state; a padded row's ``o`` means nothing. The
    run is walked in pieces of ``_PIECE`` rows (rounded up to a power of
    two, the last piece padded with rows that move nothing), the ``G``
    states carried between them."""
    t = q.shape[0]
    g = state.shape[0]
    oh = tok_seg[:, None] == jnp.arange(g)[None, :]
    live = jnp.any(oh, axis=1)
    # a padded row moves nothing
    log_a = jnp.where(live[:, None, None], log_a.astype(_F32), 0.0)
    b = jnp.where(live[:, None], b.astype(_F32), 0.0)
    q, k, v = q.astype(_F32), k.astype(_F32), v.astype(_F32)
    chunk = _piece_rows(min(_PIECE, t))
    pad = -t % chunk if t > chunk else _piece_rows(t) - t
    if pad:     # rows that move nothing: no decay, no write, no sequence
        q, k, v, log_a, b, oh = (
            jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1))
            for x in (q, k, v, log_a, b, oh))
    os = []
    for lo in range(0, t + pad, chunk):
        s = slice(lo, lo + chunk)
        o, state = _kda_block(q[s], k[s], v[s], log_a[s], b[s], oh[s],
                              state)
        os.append(o)
    o = os[0] if len(os) == 1 else jnp.concatenate(os)
    return o[:t], state


def kda_chunk_gathered(q, k, v, log_a, b, state, tok_seg, seg_rows, fresh):
    """:func:`kda_chunked` over ONE layer's whole state array ``state``
    [S, H, K, V]: the rows ``seg_rows`` [G] of the run's sequences are
    gathered (each carried in and out once), those of a ``fresh`` [G]
    sequence zeroed, advanced and scattered back. What an engine's programs
    run for their packed prompt rows."""
    carried = jnp.where(fresh[:, None, None, None], 0.0, state[seg_rows])
    o, new = kda_chunked(q, k, v, log_a, b, carried, tok_seg)
    return o, state.at[seg_rows].set(new)
