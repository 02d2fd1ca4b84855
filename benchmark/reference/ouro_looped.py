"""Ouro (``model_type: ouro``, config.json of ByteDance/Ouro-2.6B;
arXiv:2510.25741) in plain ``jax.numpy``: float32, ``precision="highest"``
on every matrix product, no kernels, no cache, no batching tricks, and THE
LOOPS WRITTEN OUT: passes outside, layers inside, every pass a full causal
attention over the whole sequence with that pass's own K and V. It imports
nothing of the program. It reads the weights the benchmark made
(``benchmark/weights_looped.py``), upcast one layer at a time, so that the
whole model in float32 (10.7 GB) never stands beside the arrays the program
holds.

``x = E[ids]``; for pass ``t = 0 .. steps - 1``, for layer ``l``::

    a = Wo Attn(RoPE(Wq n1(x)), RoPE(Wk n1(x)), Wv n1(x));   x = x + n2(a)
    m = Wdown (silu(Wgate n3(x)) * (Wup n3(x)));              x = x + n4(m)

four RMSNorms a layer (a sandwich: n2 and n4 on the branch's output before
the residual add), eps from the configuration; heads of ``hd``, causal,
scale ``1 / sqrt(hd)``, RoPE over the whole head (rotate-half, base
``theta``), no biases. After the last layer of a pass ``h_t = norm_f(x)``,
the NEXT PASS STARTS FROM ``h_t``, and ``lambda_t = sigmoid(w_g . h_t +
b_g)``. ``logits = W_head h_last``. Exit distribution ``p(t) = lambda_t
prod_{j<t} (1 - lambda_j)`` before the last pass, the rest on the last; the
exit step is the first ``t`` whose cumulative ``p`` reaches ``threshold``,
the last pass if none does.

Departures: none from the equations above (the configuration file lists
which of them were written from memory of the source).

``quant`` is the control of "How correct is decided": ``"fp8"`` rounds both
operands of every linear layer (the head among them) to float8 e4m3 with one
scale a tensor, the step below bfloat16.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from .gpt_dense import HI, _Static, mm

F32 = jnp.float32


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * w


def silu(x):
    return x * jax.nn.sigmoid(x)


def rope(x, theta):
    """``x`` [B, S, heads, hd] at positions ``0 .. S - 1``."""
    s, hd = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd)
    ang = jnp.arange(s, dtype=F32)[:, None] * inv[None, :]
    ang = jnp.concatenate([ang, ang], -1)[None, :, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return x * jnp.cos(ang) + jnp.concatenate([-x2, x1], -1) * jnp.sin(ang)


def attention(u, lp, d, quant=None):
    b, s, _ = u.shape
    heads, kvh, hd = d["heads"], d["kv_heads"], d["hd"]
    qkv = mm(u, lp["attn.qkv_proj.weight"], quant)
    q, k, v = jnp.split(qkv, [heads * hd, (heads + kvh) * hd], -1)
    q = rope(q.reshape(b, s, heads, hd), d["theta"])
    k = rope(k.reshape(b, s, kvh, hd), d["theta"])
    k = jnp.repeat(k, heads // kvh, 2)
    v = jnp.repeat(v.reshape(b, s, kvh, hd), heads // kvh, 2)
    causal = jnp.tril(jnp.ones((s, s), bool))

    def one(qkv_row):                       # a row at a time: [h, S, S]
        qr, kr, vr = qkv_row
        sc = jnp.einsum("qhd,khd->hqk", qr, kr, precision=HI) \
            / math.sqrt(hd)
        p = jax.nn.softmax(jnp.where(causal, sc, -jnp.inf), -1)
        return jnp.einsum("hqk,khd->qhd", p, vr, precision=HI)

    a = jax.lax.map(one, (q, k, v)).reshape(b, s, heads * hd)
    return mm(a, lp["attn.o_proj.weight"], quant)


def block(x, lp, d, quant=None):
    """One layer. ``x`` [B, S, H] float32; ``lp`` the layer's leaves (any
    float type)."""
    lp = {k: v.astype(F32) for k, v in lp.items()}
    eps = d["eps"]
    a = attention(rms_norm(x, lp["input_norm.weight"], eps), lp, d, quant)
    x = x + rms_norm(a, lp["post_attn_norm.weight"], eps)
    g, u = jnp.split(mm(rms_norm(x, lp["pre_mlp_norm.weight"], eps),
                        lp["mlp.gate_up.weight"], quant), 2, -1)
    m = mm(silu(g) * u, lp["mlp.down.weight"], quant)
    return x + rms_norm(m, lp["post_mlp_norm.weight"], eps)


def end_of_pass(x, top, d):
    """``(h_t, lambda_t [B, S])``."""
    h = rms_norm(x, top["final_norm.weight"].astype(F32), d["eps"])
    lam = jax.nn.sigmoid(
        jnp.matmul(h, top["gate.weight"].astype(F32), precision=HI)[..., 0]
        + top["gate.bias"].astype(F32)[0])
    return h, lam


def exit_step(lambdas, threshold):
    """``lambdas``: one [B, S] array a pass -> int32 [B, S]."""
    steps = len(lambdas)
    step = jnp.full(lambdas[0].shape, steps - 1, jnp.int32)
    undecided = jnp.ones(lambdas[0].shape, bool)
    stayed = jnp.ones(lambdas[0].shape, F32)    # prod_{j<t} (1 - lambda_j)
    cumulative = jnp.zeros(lambdas[0].shape, F32)
    for t in range(steps - 1):
        cumulative = cumulative + lambdas[t] * stayed
        stayed = stayed * (1.0 - lambdas[t])
        leaves = undecided & (cumulative >= threshold)
        step = jnp.where(leaves, t, step)
        undecided = undecided & ~leaves
    return step


def head(top, h, quant=None):
    return mm(h, top["lm_head.weight"].astype(F32), quant)


def hidden_by_layer(params: dict, ids, d, quant=None):
    """``(top leaves, h of the last pass [B, S, H], exit step [B, S])`` of
    ``ids`` [B, S]: one compiled block, a layer's weights at a time."""
    dd = _Static(d)
    top = {k: v for k, v in params.items() if not k.startswith("layers.")}
    step = jax.jit(block, static_argnums=(2, 3))
    close = jax.jit(end_of_pass, static_argnums=2)
    x = top["embed.weight"][ids].astype(F32)
    lambdas = []
    for _ in range(d["steps"]):
        for l in range(d["L"]):
            pre = f"layers.{l}."
            lp = {k[len(pre):]: v for k, v in params.items()
                  if k.startswith(pre)}
            x = step(x, lp, dd, quant)
        x, lam = close(x, top, dd)
        lambdas.append(lam)
    return top, x, exit_step(lambdas, d["threshold"])


def logits(params: dict, ids, d, quant=None):
    top, h, _ = hidden_by_layer(params, ids, d, quant)
    return head(top, h, quant)


def served_gaps(params: dict, ids, first, count, served, d, quant=None):
    """As ``gpt_dense.served_gaps``: for each row of ``ids`` [B, S] (prompt
    then served tokens, padded) and each served position ``first[b] <= t <
    first[b] + count[b]``, how far the logit of the served token lies below
    the reference's best (``gap``, ``mask``); the reference's ``exit_step``
    beside them; with ``quant`` also the gap of the token the lower
    precision puts first."""
    top, x, steps = hidden_by_layer(params, ids, d, None)
    xq = hidden_by_layer(params, ids, d, quant)[1] if quant else None

    def row(top, xr, xqr, sv, f, c):
        lg = head(top, xr)
        best = lg.max(-1)
        t = jnp.arange(lg.shape[0])
        mask = (t >= f) & (t < f + c)

        def below_best(tok):
            return jnp.where(mask, best - jnp.take_along_axis(
                lg, tok[:, None], -1)[:, 0], 0.0)

        out = {"gap": below_best(sv), "mask": mask}
        if xqr is not None:
            out["control_gap"] = below_best(
                jnp.argmax(head(top, xqr, quant), -1))
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
    return dict(jax.jit(rows)(top, xs), exit_step=steps)
