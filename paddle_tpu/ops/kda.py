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

Three forms, all plain ``jax.numpy`` (no kernel exists yet: ROADMAP A):

- :func:`kda_recurrence`: the definition, a ``lax.scan`` over the tokens of
  ONE sequence. The oracle of the other two; never on the engine's path.
- :func:`kda_step`: one token a row, the decode tick.
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

import jax
import jax.numpy as jnp

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
