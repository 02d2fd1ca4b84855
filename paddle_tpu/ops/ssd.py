"""State-space duality (Mamba-2) operators over ragged rows.

The recurrence, a head at a time (``x_t`` in R^P, ``B_t``, ``C_t`` in R^N,
``a_t = dt_t * A`` with ``A < 0``)::

    S_t = exp(a_t) S_{t-1} + dt_t x_t B_t^T          (P x N)
    y_t = S_t C_t + D x_t

(Dao & Gu 2024, "Transformers are SSMs", arXiv:2405.21060). Two forms:

- :func:`ssd_step`: one token a row, the decode tick: one fused pass that
  reads and writes the state once.
- :func:`ssd_chunked`: a PACKED run of ``T`` rows that holds up to ``G``
  sequences, each contiguous and in order (``tok_seg[t]`` = the local index
  of row ``t``'s sequence, ``G`` for a padded row). Every sequence enters
  from its own carried state and leaves its final state. The run is walked
  in blocks of ``chunk`` rows; inside a block the quadratic (attention-like)
  form is masked across sequences, between blocks the ``G`` states carry.

:func:`causal_conv_chunk` / :func:`causal_conv_step` are the depthwise causal
convolution that precedes the scan, with the carried ``d_conv - 1`` position
tail of each sequence.

Plain ``jax.numpy``: the decays, their cumulative sums and the state are
float32 whatever the activations are (a recurrence rounds at every token).
No Pallas kernel yet; on the TPU the float32 products ask for
``Precision.HIGHEST``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

_HI = jax.lax.Precision.HIGHEST


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
