"""Granite 4.0-H (``granitemoehybrid``, config.json of
ibm-granite/granite-4.0-h-small) in plain ``jax.numpy``: float32,
``precision="highest"`` on every matrix product, no kernels, no cache, no
batching tricks, and THE TOKEN-BY-TOKEN RECURRENCE for the state-space layers
(``lax.scan`` over positions, not the chunked algorithm the program runs). It
imports nothing of the program. It reads the weights the benchmark made
(``benchmark/weights_hybrid.py``), upcast a layer (an expert) at a time, so
that ten layers of float32 never stand on the chip at once.

With ``h`` the residual stream, per layer::

    u = RMSNorm_in(h);    h = h + 0.22 * Mixer(u)
    v = RMSNorm_post(h);  h = h + 0.22 * (Routed(v) + Shared(v))

``h0 = 12 * E[ids]``; ``logits = RMSNorm_f(h) @ E^T / 16`` over the held rows
of ``E``; eps 1e-5 (the four multipliers and eps are read from the
configuration).

- Attention mixer: q, k, v, o without bias, GQA, no positional encoding,
  scores times ``attention_multiplier`` (1/128, not 1/sqrt(128)), causal.
- Mamba-2 mixer: ``[z | xBC | dt] = in_proj(u)``; ``xBC = silu(conv(xBC) +
  b)`` depthwise over the last ``d_conv`` positions; ``[x | B | C] = xBC``;
  ``dt = softplus(dt + dt_bias)``; ``A = -exp(A_log)``; a head:
  ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T``, ``y_t = S_t C_t + D x_t``;
  ``y = RMSNorm(y * silu(z))`` over all of ``d_inner``; ``out_proj``.
- Experts: ``g = W_r v`` over ALL ``E`` experts; the ``top_k`` largest; gates
  = softmax over those; expert ``W_out (silu(a) * b)``, ``[a | b] = W_in v``;
  ``Routed`` sums the chosen experts among those HELD (``first .. first +
  count``): the chip's share of the deployment, as the program computes it.
  ``Shared`` the same form, every row.

Departures: none from the equations above; ``time_step_limit`` (0, inf).

``quant`` is the control of "How correct is decided": ``"fp8"`` rounds both
operands of every linear layer (the router and the head among them) to
float8 e4m3 with one scale a tensor, the step below bfloat16.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .gpt_dense import HI, _Static, mm

F32 = jnp.float32


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * w


def silu(x):
    return x * jax.nn.sigmoid(x)


def attention_mixer(u, lp, d, quant=None):
    b, s, h = u.shape
    heads, kvh, hd = d["heads"], d["kv_heads"], d["hd"]
    qkv = mm(u, lp["mixer.qkv_proj.weight"], quant)
    q, k, v = jnp.split(qkv, [h, h + kvh * hd], -1)
    q = q.reshape(b, s, heads, hd)
    k = jnp.repeat(k.reshape(b, s, kvh, hd), heads // kvh, 2)
    v = jnp.repeat(v.reshape(b, s, kvh, hd), heads // kvh, 2)
    causal = jnp.tril(jnp.ones((s, s), bool))

    def one(qkv_row):                       # a row at a time: [h, S, S]
        qr, kr, vr = qkv_row
        sc = jnp.einsum("qhd,khd->hqk", qr, kr, precision=HI) \
            * d["attention_multiplier"]
        p = jax.nn.softmax(jnp.where(causal, sc, -jnp.inf), -1)
        return jnp.einsum("hqk,khd->qhd", p, vr, precision=HI)

    a = jax.lax.map(one, (q, k, v)).reshape(b, s, h)
    return mm(a, lp["mixer.o_proj.weight"], quant)


def mamba_mixer(u, lp, d, quant=None):
    b, s, _ = u.shape
    di, cd, nh, dh, n, kk = (d["di"], d["cd"], d["nh"], d["dh"], d["N"],
                             d["K"])
    z, xbc, dt = jnp.split(mm(u, lp["mixer.in_proj.weight"], quant),
                           [di, di + cd], -1)
    # causal depthwise convolution over the last K positions
    pad = jnp.concatenate([jnp.zeros((b, kk - 1, cd), F32), xbc], 1)
    w = lp["mixer.conv_weight"]
    conv = sum(pad[:, k:k + s] * w[k] for k in range(kk)) \
        + lp["mixer.conv_bias"]
    x, bm, cm = jnp.split(silu(conv), [di, di + n], -1)
    x = x.reshape(b, s, nh, dh)
    dt = jax.nn.softplus(dt + lp["mixer.dt_bias"])          # [B, S, nh]
    a = -jnp.exp(lp["mixer.A_log"])

    def step(state, inp):
        xt, dtt, bt, ct = inp                # [B,nh,dh] [B,nh] [B,N] [B,N]
        state = jnp.exp(dtt * a)[:, :, None, None] * state \
            + (dtt[:, :, None] * xt)[..., None] * bt[:, None, None, :]
        return state, jnp.sum(state * ct[:, None, None, :], -1)

    _, y = jax.lax.scan(
        step, jnp.zeros((b, nh, dh, n), F32),
        (x.swapaxes(0, 1), dt.swapaxes(0, 1), bm.swapaxes(0, 1),
         cm.swapaxes(0, 1)))
    y = y.swapaxes(0, 1) + lp["mixer.D"][None, None, :, None] * x
    y = rms_norm(y.reshape(b, s, di) * silu(z), lp["mixer.norm_weight"],
                 d["eps"])
    return mm(y, lp["mixer.out_proj.weight"], quant)


def gated_mlp(v, w_in, w_out, quant=None):
    a, b = jnp.split(mm(v, w_in, quant), 2, -1)
    return mm(silu(a) * b, w_out, quant)


def routed(v, lp, d, quant=None):
    """The held experts' part of the routed sum, an expert at a time."""
    logits = mm(v, lp["moe.router"], quant)                  # [B, S, E]
    top, idx = jax.lax.top_k(logits, d["top_k"])
    gates = jax.nn.softmax(top, -1)

    def one(acc, xs):
        e, w_in, w_out = xs
        gate = jnp.sum(jnp.where(idx == e, gates, 0.0), -1, keepdims=True)
        return acc + gate * gated_mlp(v, w_in.astype(F32),
                                      w_out.astype(F32), quant), None

    ids = d["first"] + jnp.arange(d["count"])
    out, _ = jax.lax.scan(one, jnp.zeros_like(v),
                          (ids, lp["moe.w_in"], lp["moe.w_out"]))
    return out


def block(x, lp, kind: str, d, quant=None):
    """One layer. ``x`` [B, S, H] float32; ``lp`` the layer's leaves (any
    float type; the stacked experts are upcast one at a time)."""
    lp = {k: (v if k in ("moe.w_in", "moe.w_out") else v.astype(F32))
          for k, v in lp.items()}
    r = d["residual_multiplier"]
    u = rms_norm(x, lp["input_norm.weight"], d["eps"])
    mixer = attention_mixer if kind == "attention" else mamba_mixer
    x = x + r * mixer(u, lp, d, quant)
    v = rms_norm(x, lp["post_norm.weight"], d["eps"])
    return x + r * (routed(v, lp, d, quant)
                    + gated_mlp(v, lp["shared.w_in.weight"],
                                lp["shared.w_out.weight"], quant))


def embed(top, ids, d):
    return top["embed.weight"][ids].astype(F32) * d["embedding_multiplier"]


def head(top, x, d, quant=None):
    y = rms_norm(x, top["final_norm.weight"].astype(F32), d["eps"])
    return mm(y, top["embed.weight"].astype(F32).T, quant) \
        / d["logits_scaling"]


def _sizes(d) -> _Static:
    """The sizes without the tuple of layer kinds: hashable, so static."""
    return _Static({k: v for k, v in d.items() if k != "kinds"})


def hidden_by_layer(params: dict, ids, d, quant=None):
    """Final hidden states [B, S, H] of ``ids`` [B, S], a layer at a time
    through one compiled block a kind of layer."""
    dd = _sizes(d)
    top = {k: v for k, v in params.items() if not k.startswith("layers.")}
    x = jax.jit(embed, static_argnums=2)(top, ids, dd)
    step = jax.jit(block, static_argnums=(2, 3, 4))
    for l, kind in enumerate(d["kinds"]):
        pre = f"layers.{l}."
        lp = {k[len(pre):]: v for k, v in params.items()
              if k.startswith(pre)}
        x = step(x, lp, kind, dd, quant)
    return top, x


def logits(params: dict, ids, d, quant=None):
    top, x = hidden_by_layer(params, ids, d, quant)
    return head(top, x, _sizes(d), quant)


def served_gaps(params: dict, ids, first, count, served, d, quant=None):
    """As ``gpt_dense.served_gaps``: for each row of ``ids`` [B, S] (prompt
    then served tokens, padded) and each served position ``first[b] <= t <
    first[b] + count[b]``, how far the logit of the served token lies below
    the reference's best; with ``quant`` also the gap of the token the lower
    precision puts first."""
    dd = _sizes(d)
    top, x = hidden_by_layer(params, ids, d, None)
    xq = hidden_by_layer(params, ids, d, quant)[1] if quant else None

    def row(top, xr, xqr, sv, f, c):
        lg = head(top, xr, dd)
        best = lg.max(-1)
        t = jnp.arange(lg.shape[0])
        mask = (t >= f) & (t < f + c)

        def below_best(tok):
            return jnp.where(mask, best - jnp.take_along_axis(
                lg, tok[:, None], -1)[:, 0], 0.0)

        out = {"gap": below_best(sv), "mask": mask}
        if xqr is not None:
            out["control_gap"] = below_best(
                jnp.argmax(head(top, xqr, dd, quant), -1))
        return out

    # one row at a time, so that no [B, S, V] array exists
    if xq is None:
        rows = lambda top, xs: jax.lax.map(  # noqa: E731
            lambda a: row(top, a[0], None, *a[1:]), xs)
        xs = (x, served, first, count)
    else:
        rows = lambda top, xs: jax.lax.map(  # noqa: E731
            lambda a: row(top, *a), xs)
        xs = (x, xq, served, first, count)
    return jax.jit(rows)(top, xs)

