"""Kimi Linear (``model_type: kimi_linear``, config.json of
moonshotai/Kimi-Linear-48B-A3B-Instruct) in plain ``jax.numpy``: float32,
``precision="highest"`` on every matrix product, no kernels, no cache, no
pages, no chunk form. It imports nothing of the program. It reads the weights
the benchmark made (``benchmark/weights_kda.py``), upcast one layer at a time
(the stacked experts one expert at a time).

``x = E[ids]``; ``u = RMSNorm(x)`` before each branch (ASSUMED: pre-norm);
layer ``l`` of ``kinds[l]``::

    kda:  [q | k | v] = silu(conv(u W_qkv))   depthwise causal conv over the
              last `conv` positions, no bias (ASSUMED: on q, k and v)
          q, k = L2norm(q), L2norm(k) a head (ASSUMED: after silu)
          log a = -exp(A_log[h]) softplus((u W_f1) W_f2 + dt_bias)  a channel
          b = sigmoid(u W_b)                                        a head
          S_t = (I - b_t k_t k_t^T) Diag(a_t) S_{t-1} + b_t k_t v_t^T,
          o_t = S_t^T q_t / sqrt(d)          TOKEN BY TOKEN (a lax.scan)
          y = (RMSNorm_head(o) * sigmoid((u W_g1) W_g2 + c_g)) W_o
    mla:  q = u W_q [heads, nope + rope];  [c | r] = u W_kva;  c <- RMSNorm(c)
          [k_nope_h | v_h] = c W_kvb,h;  k_h = [k_nope_h | r]   (r NOT rotated:
          mla_use_nope; no position anywhere in the layer)
          EXPANDED over the whole sequence: scores q_h . k_h / sqrt(nope +
          rope), row p sees j <= p, softmax, o_h = sum_j p_j v_j;
          y = concat_h(o_h) W_o
    x = x + y;  x = x + FF(RMSNorm(x))

``FF`` of a layer in ``dense``: ``W_out (silu(a) * b)``, ``[a | b] = W_in
u``. Any other: ``shared(u) + routed_scale * sum_{e in top k} g_e E_e(u)``:
scores ``s = sigmoid(u W_r)`` over ALL ``E`` experts, chosen the ``top_k``
largest of ``s + e_bias`` (the bias is in no gate; one group, so no group
limit), ``g = s`` of the chosen over their sum; only the experts ``first ..
first + count - 1`` are held and summed (the guide's section 4: all scored,
``top_k`` taken, the held ones add; the absent chips' share is left out, in
the program alike). Final RMSNorm, untied head.

Departures from the published description: the fused ``W_qkv`` (the
program's leaf; the same numbers as three matrices); everything under
ASSUMED above and in the configuration's ``assumed``.

So that 7,680 tokens fit: a sequence at a time through the layers, the MLA
scores a block of ``QUERY_BLOCK`` queries at a time against every key, so
that no ``[heads, S, S]`` array ever exists; the delta rule carries one
``[heads, d, d]`` state through a scan over the tokens.

``quant`` is the control of "How correct is decided": ``"fp8"`` rounds both
operands of every linear layer (router, gates and head among them) to float8
e4m3 with one scale a tensor, the step below bfloat16; ``"bf16"`` rounds
them to bfloat16, the program's own precision (no control: the floor the
program's gap is read against). Two more, for the program's state:
``"state_bf16"`` rounds the delta rule's state to bfloat16 after every
token, ``"decay_bf16"`` rounds ``log a``. A ``+`` joins a linear one and
state ones (``"bf16+state_bf16"``: a state fault inside the program's own
precision).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from .gpt_dense import HI, _Static
from .gpt_dense import mm as _mm

F32 = jnp.float32
QUERY_BLOCK = 512
LINEAR_QUANTS = ("fp8", "bf16")


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * w


def silu(x):
    return x * jax.nn.sigmoid(x)


def l2norm(x, eps=1e-6):
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + eps)


def _has(quant, name: str) -> bool:
    return name in (quant or "").split("+")


def _linear_quant(quant):
    """The linear layers' part of a control's name, or None."""
    return next((q for q in LINEAR_QUANTS if _has(quant, q)), None)


def _as_bf16(x):
    """``x`` rounded to bfloat16's eight bits of significand, still float32.
    Not ``astype`` there and back: XLA:TPU may keep excess precision and
    takes such a pair of converts out (both state controls read the same
    numbers to the last digit on the chip, my chip runs, PR 39)."""
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


def mm(a, b, quant=None):
    """``gpt_dense.mm``, but for ``"bf16"``, which that one rounds with the
    pair of converts :func:`_as_bf16` is there to avoid."""
    if quant == "bf16":
        return jnp.matmul(_as_bf16(a), _as_bf16(b), precision=HI)
    return _mm(a, b, quant)


def causal_conv(x, w):
    """``x`` [S, C], ``w`` [K, C] (``w[K - 1]`` multiplies the current
    token): ``y_t = sum_j w[K - 1 - j] x_{t - j}``, zeros before the
    sequence."""
    k = w.shape[0]
    padded = jnp.concatenate([jnp.zeros((k - 1, x.shape[1]), F32), x])
    return sum(padded[j:j + x.shape[0]] * w[j] for j in range(k))


def delta_rule(q, k, v, log_a, b, round_state=False):
    """The recurrence, token by token, from a zero state: ``q``, ``k``,
    ``log_a`` [S, heads, d], ``v`` [S, heads, d], ``b`` [S, heads]."""
    def step(s, xs):
        qt, kt, vt, at, bt = xs
        s = jnp.exp(at)[..., None] * s
        s = s + (bt[:, None] * kt)[..., None] * (
            vt - jnp.einsum("hk,hkv->hv", kt, s, precision=HI))[:, None, :]
        if round_state:
            s = _as_bf16(s)
        return s, jnp.einsum("hk,hkv->hv", qt, s, precision=HI)

    h, dk = q.shape[1:]
    _, o = jax.lax.scan(step, jnp.zeros((h, dk, v.shape[-1]), F32),
                        (q, k, v, log_a, b))
    return o


def kda(u, lp, d, quant=None):
    """``u`` [S, H] -> the KDA branch's output [S, H]."""
    lq = _linear_quant(quant)
    s = u.shape[0]
    nh, hd = d["kda_heads"], d["kda_hd"]
    qkv = silu(causal_conv(mm(u, lp["mixer.qkv_proj.weight"], lq),
                           lp["mixer.conv_weight"]))
    q, k, v = (t.reshape(s, nh, hd) for t in jnp.split(qkv, 3, -1))
    q, k = l2norm(q) / math.sqrt(hd), l2norm(k)
    f = mm(mm(u, lp["mixer.f_a.weight"], lq), lp["mixer.f_b.weight"], lq) \
        + lp["mixer.dt_bias"]
    log_a = -jnp.exp(lp["mixer.A_log"])[None, :, None] \
        * jax.nn.softplus(f).reshape(s, nh, hd)
    if _has(quant, "decay_bf16"):
        log_a = _as_bf16(log_a)
    b = jax.nn.sigmoid(mm(u, lp["mixer.b_proj.weight"], lq))
    o = delta_rule(q, k, v, log_a, b, _has(quant, "state_bf16"))
    gate = jax.nn.sigmoid(
        mm(mm(u, lp["mixer.g_a.weight"], lq), lp["mixer.g_b.weight"], lq)
        + lp["mixer.g_bias"])
    o = rms_norm(o, lp["mixer.o_norm_weight"], d["eps"])
    return mm(o.reshape(s, nh * hd) * gate, lp["mixer.o_proj.weight"], lq)


def mla(u, lp, d, quant=None):
    """``u`` [S, H] -> the MLA branch's output [S, H], expanded."""
    lq = _linear_quant(quant)
    s = u.shape[0]
    n, nope, rope, vd = d["heads"], d["nope"], d["rope"], d["vd"]
    q = mm(u, lp["mixer.q_proj.weight"], lq).reshape(s, n, nope + rope)
    c, r = jnp.split(mm(u, lp["mixer.kv_a.weight"], lq), [d["lora"]], -1)
    c = rms_norm(c, lp["mixer.kv_norm_weight"], d["eps"])
    kv = mm(c, lp["mixer.kv_b.weight"], lq).reshape(s, n, nope + vd)
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(r[:, None], (s, n, rope))], -1)
    v = kv[..., nope:]
    blk = min(s, QUERY_BLOCK)
    if s % blk:
        raise ValueError(f"sequence length {s} is no multiple of {blk}")

    def block_of_queries(xs):
        qb, first = xs                                   # [blk, n, nope+rope]
        seen = (first + jnp.arange(blk))[:, None] >= jnp.arange(s)[None, :]
        sc = jnp.einsum("qhd,khd->hqk", qb, k, precision=HI) \
            / math.sqrt(nope + rope)
        p = jax.nn.softmax(jnp.where(seen, sc, -jnp.inf), -1)
        return jnp.einsum("hqk,khv->qhv", p, v, precision=HI)

    o = jax.lax.map(block_of_queries,
                    (q.reshape(s // blk, blk, n, nope + rope),
                     jnp.arange(s // blk) * blk))
    return mm(o.reshape(s, n * vd), lp["mixer.o_proj.weight"], lq)


def gated_mlp(v, w_in, w_out, quant=None):
    a, b = jnp.split(mm(v, w_in, quant), 2, -1)
    return mm(silu(a) * b, w_out, quant)


def route(v, lp, d, quant=None):
    """``(expert ids [.., k], gates [.., k])``: sigmoid scores over all
    experts, the ``top_k`` largest of score + bias, the scores of those over
    their sum, times the scale."""
    scores = jax.nn.sigmoid(mm(v, lp["moe.router"], quant))
    _, idx = jax.lax.top_k(scores + lp["moe.e_bias"], d["top_k"])
    top = jnp.take_along_axis(scores, idx, -1)
    return idx, top / jnp.sum(top, -1, keepdims=True) * d["routed_scale"]


def routed(v, lp, d, quant=None, held=None):
    """The part of the routed sum that experts ``held = (first, count)``
    give (the configuration's own when None), an expert at a time."""
    first, count = held or (d["first"], d["count"])
    idx, gates = route(v, lp, d, quant)

    def one(acc, xs):
        e, w_in, w_out = xs
        gate = jnp.sum(jnp.where(idx == e, gates, 0.0), -1, keepdims=True)
        return acc + gate * gated_mlp(v, w_in.astype(F32),
                                      w_out.astype(F32), quant), None

    out, _ = jax.lax.scan(one, jnp.zeros_like(v),
                          (first + jnp.arange(count), lp["moe.w_in"],
                           lp["moe.w_out"]))
    return out


def block(x, lp, kind: str, dense: bool, d, quant=None):
    """One layer. ``x`` [S, H] float32; ``lp`` the layer's leaves (any float
    type; the stacked experts are upcast one at a time)."""
    lp = {k: (v if k in ("moe.w_in", "moe.w_out") else v.astype(F32))
          for k, v in lp.items()}
    lq = _linear_quant(quant)
    u = rms_norm(x, lp["input_norm.weight"], d["eps"])
    x = x + (kda if kind == "kda" else mla)(u, lp, d, quant)
    u = rms_norm(x, lp["post_norm.weight"], d["eps"])
    if dense:
        return x + gated_mlp(u, lp["mlp.w_in.weight"],
                             lp["mlp.w_out.weight"], lq)
    return x + routed(u, lp, d, lq) + gated_mlp(
        u, lp["shared.w_in.weight"], lp["shared.w_out.weight"], lq)


def head(top, x, d, quant=None):
    y = rms_norm(x, top["final_norm.weight"].astype(F32), d["eps"])
    return mm(y, top["lm_head.weight"].astype(F32), _linear_quant(quant))


def _sizes(d) -> _Static:
    """The sizes without the per-layer tuples: hashable, so static."""
    return _Static({k: v for k, v in d.items()
                    if k not in ("kinds", "dense")})


def hidden_by_layer(params: dict, ids, d, quant=None):
    """Final hidden states [B, S, H] of ``ids`` [B, S]: a sequence at a
    time, a layer at a time through one compiled block a shape of layer."""
    dd = _sizes(d)
    top = {k: v for k, v in params.items() if not k.startswith("layers.")}
    step = jax.jit(block, static_argnums=(2, 3, 4, 5))
    layers = []
    for l in range(d["L"]):
        pre = f"layers.{l}."
        layers.append({k[len(pre):]: v for k, v in params.items()
                       if k.startswith(pre)})
    rows = []
    for row in ids:
        x = top["embed.weight"][jnp.asarray(row)].astype(F32)
        for l, lp in enumerate(layers):
            x = step(x, lp, d["kinds"][l], l in d["dense"], dd, quant)
        rows.append(x)
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
