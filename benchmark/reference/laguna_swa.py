"""Laguna (``model_type: laguna``, config.json of poolside/Laguna-S-2.1) in
plain ``jax.numpy``: float32, ``precision="highest"`` on every matrix
product, no kernels, no cache, no pages. It imports nothing of the program.
It reads the weights the benchmark made (``benchmark/weights_swa.py``),
upcast one layer at a time (the stacked experts one expert at a time).

``x = E[ids]``; ``u = RMSNorm(x)`` before each branch (ASSUMED: pre-norm);
layer ``l`` of ``kinds[l]`` with ``heads[l]`` query heads, ``kv_heads`` K/V
heads of ``hd``::

    q = u W_q, k = u W_k, v = u W_v        (ASSUMED: no Q/K norm, no biases)
    sliding: RoPE over the whole head, base sliding_theta
    full:    the first full_rot dimensions of a head rotate (rotate-half
             inside them), the rest pass; inverse frequencies YaRN's:
             f_i = full_theta^(-2i / full_rot),
             low  = floor(full_rot ln(original / (beta_fast 2 pi))
                          / (2 ln full_theta)),
             high = ceil(full_rot ln(original / (beta_slow 2 pi))
                         / (2 ln full_theta)), clamped to [0, full_rot - 1],
             m_i = 1 - clip((i - low) / (high - low), 0, 1),
             inv_freq_i = (f_i / factor)(1 - m_i) + f_i m_i;
             cos and sin times attention_factor
    scores q . k / sqrt(hd); row p sees j iff j <= p, and on a sliding layer
    also p - j < window; softmax; o_h = sum_j p_j v_j
    g = sigmoid(u W_g), one scalar a head (ASSUMED form of "per-head"
        gating: sigmoid of a linear map of the normed input, before W_o)
    x = x + concat_h(g_h o_h) W_o
    x = x + FF(RMSNorm(x))

``FF`` of a layer in ``dense``: ``W_out (silu(a) * b)``, ``[a | b] = W_in
u``. Any other: ``shared(u) + routed_scale * sum_{e in top k} g_e E_e(u)``,
the router's scores over all ``E`` experts, the ``top_k`` largest, ``g`` =
softmax over those (ASSUMED: softmax as the router's activation, no router
bias, no gate on the shared expert); only the experts ``first .. first +
count - 1`` are held and summed. Final RMSNorm, untied head.

So that 9k tokens fit: a sequence at a time through the layers, and
attention a block of ``QUERY_BLOCK`` queries at a time (against every key on
a full layer; on a sliding one against the ``window`` keys before the block
and the block's own, which are all it can see), so that no ``[heads, S, S]``
score array ever exists.

``quant`` is the control of "How correct is decided": ``"fp8"`` rounds both
operands of every linear layer (router, gate and head among them) to float8
e4m3 with one scale a tensor, the step below bfloat16.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from .gpt_dense import HI, _Static, mm

F32 = jnp.float32
FULL, SLIDING = "full_attention", "sliding_attention"
QUERY_BLOCK = 512


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * w


def silu(x):
    return x * jax.nn.sigmoid(x)


def yarn_inv_freq(d):
    rot, theta = d["full_rot"], d["full_theta"]
    i = jnp.arange(rot // 2, dtype=F32)
    f = theta ** (-2.0 * i / rot)

    def turn_dim(turns):
        return rot * math.log(d["yarn_original"] / (turns * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(turn_dim(d["yarn_beta_fast"])), 0)
    high = min(math.ceil(turn_dim(d["yarn_beta_slow"])), rot - 1)
    m = 1.0 - jnp.clip((i - low) / max(high - low, 1e-3), 0.0, 1.0)
    return (f / d["yarn_factor"]) * (1.0 - m) + f * m


def rotate(x, inv_freq, factor=1.0):
    """``x`` [S, heads, hd] at positions ``0 .. S - 1``: the first ``2 *
    len(inv_freq)`` dimensions of each head rotate, the rest pass."""
    rot = 2 * inv_freq.shape[0]
    ang = jnp.arange(x.shape[0], dtype=F32)[:, None] * inv_freq[None, :]
    ang = jnp.concatenate([ang, ang], -1)[:, None, :]
    xr = x[..., :rot]
    x1, x2 = xr[..., :rot // 2], xr[..., rot // 2:]
    xr = xr * (jnp.cos(ang) * factor) \
        + jnp.concatenate([-x2, x1], -1) * (jnp.sin(ang) * factor)
    return jnp.concatenate([xr, x[..., rot:]], -1)


def attention(u, lp, kind: str, heads: int, d, quant=None):
    """``u`` [B, S, H] -> the attention branch's output [B, S, H]."""
    b, s, _ = u.shape
    kvh, hd = d["kv_heads"], d["hd"]
    grp = heads // kvh
    qkv = mm(u, lp["attn.qkv_proj.weight"], quant)
    q, k, v = jnp.split(qkv, [heads * hd, (heads + kvh) * hd], -1)
    gate = jax.nn.sigmoid(mm(u, lp["attn.g_proj.weight"], quant))
    if kind == SLIDING:
        inv = d["sliding_theta"] ** (
            -jnp.arange(0, d["sliding_rot"], 2, dtype=F32)
            / d["sliding_rot"])
        factor, window = 1.0, d["window"]
    else:
        inv, factor, window = yarn_inv_freq(d), d["attention_factor"], None
    blk = min(s, QUERY_BLOCK)
    if s % blk:
        raise ValueError(f"sequence length {s} is no multiple of {blk}")

    def one(row):                            # a sequence at a time
        qr, kr, vr = row
        qr = rotate(qr.reshape(s, heads, hd), inv, factor) \
            .reshape(s // blk, blk, kvh, grp, hd)
        kr = rotate(kr.reshape(s, kvh, hd), inv, factor)
        vr = vr.reshape(s, kvh, hd)
        if window is not None and s > blk + window:
            # the keys a block can see: `window` before it and its own
            front = jnp.zeros((window, kvh, hd), F32)
            kr, vr = jnp.concatenate([front, kr]), jnp.concatenate(
                [front, vr])
            span = blk + window
        else:
            span = None

        def block_of_queries(xs):
            qb, first = xs                   # [blk, kvh, grp, hd]
            if span is None:
                kb, vb, keys = kr, vr, jnp.arange(s)
            else:
                kb = jax.lax.dynamic_slice_in_dim(kr, first, span)
                vb = jax.lax.dynamic_slice_in_dim(vr, first, span)
                keys = first - window + jnp.arange(span)
            back = (first + jnp.arange(blk))[:, None] - keys[None, :]
            seen = (back >= 0) & (keys >= 0)[None, :]
            if window is not None:
                seen = seen & (back < window)
            sc = jnp.einsum("qkgd,skd->kgqs", qb, kb, precision=HI) \
                / math.sqrt(hd)
            p = jax.nn.softmax(jnp.where(seen, sc, -jnp.inf), -1)
            return jnp.einsum("kgqs,skd->qkgd", p, vb, precision=HI)

        o = jax.lax.map(block_of_queries,
                        (qr, jnp.arange(s // blk) * blk))
        return o.reshape(s, heads, hd)       # head h = kv h // grp: [k, g]

    o = jax.lax.map(one, (q, k, v))
    o = (o * gate[..., None]).reshape(b, s, heads * hd)
    return mm(o, lp["attn.o_proj.weight"], quant)


def gated_mlp(v, w_in, w_out, quant=None):
    a, b = jnp.split(mm(v, w_in, quant), 2, -1)
    return mm(silu(a) * b, w_out, quant)


def routed(v, lp, d, quant=None, held=None):
    """The part of the routed sum that experts ``held = (first, count)``
    give (the configuration's own when None), an expert at a time."""
    first, count = held or (d["first"], d["count"])
    logits = mm(v, lp["moe.router"], quant)                  # [B, S, E]
    top, idx = jax.lax.top_k(logits, d["top_k"])
    gates = jax.nn.softmax(top, -1) * d["routed_scale"]

    def one(acc, xs):
        e, w_in, w_out = xs
        gate = jnp.sum(jnp.where(idx == e, gates, 0.0), -1, keepdims=True)
        return acc + gate * gated_mlp(v, w_in.astype(F32),
                                      w_out.astype(F32), quant), None

    out, _ = jax.lax.scan(one, jnp.zeros_like(v),
                          (first + jnp.arange(count), lp["moe.w_in"],
                           lp["moe.w_out"]))
    return out


def block(x, lp, kind: str, heads: int, dense: bool, d, quant=None):
    """One layer. ``x`` [B, S, H] float32; ``lp`` the layer's leaves (any
    float type; the stacked experts are upcast one at a time)."""
    lp = {k: (v if k in ("moe.w_in", "moe.w_out") else v.astype(F32))
          for k, v in lp.items()}
    u = rms_norm(x, lp["input_norm.weight"], d["eps"])
    x = x + attention(u, lp, kind, heads, d, quant)
    u = rms_norm(x, lp["post_norm.weight"], d["eps"])
    if dense:
        return x + gated_mlp(u, lp["mlp.w_in.weight"],
                             lp["mlp.w_out.weight"], quant)
    return x + routed(u, lp, d, quant) + gated_mlp(
        u, lp["shared.w_in.weight"], lp["shared.w_out.weight"], quant)


def head(top, x, d, quant=None):
    y = rms_norm(x, top["final_norm.weight"].astype(F32), d["eps"])
    return mm(y, top["lm_head.weight"].astype(F32), quant)


def _sizes(d) -> _Static:
    """The sizes without the per-layer tuples: hashable, so static."""
    return _Static({k: v for k, v in d.items()
                    if k not in ("kinds", "heads", "dense")})


def hidden_by_layer(params: dict, ids, d, quant=None):
    """Final hidden states [B, S, H] of ``ids`` [B, S]: a sequence at a
    time, a layer at a time through one compiled block a shape of layer."""
    dd = _sizes(d)
    top = {k: v for k, v in params.items() if not k.startswith("layers.")}
    step = jax.jit(block, static_argnums=(2, 3, 4, 5, 6))
    layers = []
    for l in range(d["L"]):
        pre = f"layers.{l}."
        layers.append({k[len(pre):]: v for k, v in params.items()
                       if k.startswith(pre)})
    rows = []
    for row in ids:
        x = top["embed.weight"][jnp.asarray(row)[None]].astype(F32)
        for l, lp in enumerate(layers):
            x = step(x, lp, d["kinds"][l], d["heads"][l], l in d["dense"],
                     dd, quant)
        rows.append(x[0])
    return top, jnp.stack(rows)


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
