"""MiMo-V2-Flash (``model_type: mimo_v2_flash``, config.json of
XiaomiMiMo/MiMo-V2-Flash) in plain ``jax.numpy``: float32,
``precision="highest"`` on every matrix product, no kernels, no cache, no
pages. It imports nothing of the program. It reads the weights the benchmark
made (``benchmark/weights_mimo.py``), upcast one layer at a time (the stacked
experts one expert at a time).

``x = E[ids]``; ``u = RMSNorm(x)``, eps 1e-5, before each branch (ASSUMED:
pre-norm, a final norm); no biases; layer ``l`` of ``kinds[l]`` (ASSUMED:
``hybrid_layer_pattern`` 1 = sliding)::

    q = u W_q [T, 64, 192];  k = u W_k [T, kv, 192]     (ASSUMED: no Q/K norm)
    v = vscale * u W_v [T, kv, 128]                kv = 4 full | 8 sliding
        (ASSUMED: attention_value_scale on v; on a head's output it is the
        same number)
    q, k: RoPE over the first int(0.334 * 192) = 64 dimensions (rotate-half
          inside them), base 5e6 full | 1e4 sliding
    s_hj = q_h . k_j / sqrt(192),  j <= p, and on a sliding layer p - j < 128
    full:    o_h = softmax_j(s_hj) v_j
    sliding: o_h = sum_j e^{s_hj - m} v_j / (e^{b_h - m} + sum_j e^{s_hj - m})
             m = max(b_h, max_j s_hj)
        (ASSUMED form of add_swa_attention_sink_bias: one float32 logit a
        query head that joins the denominator and carries no value)
    x = x + concat_h(o_h) W_o
    x = x + FF(RMSNorm(x))

``FF`` of a layer in ``dense``: ``W_out (silu(a) * b)``, ``[a | b] = W_in
u``. Any other: ``sum_{e in top k} g_e E_e(u)``: scores ``z = sigmoid(u W_r)``
over ALL ``E`` experts, chosen the ``top_k`` largest of ``z + e_bias`` (the
bias is in no gate; ``n_group`` 1: no group limit), ``g = z`` of the chosen
over their sum (``norm_topk_prob``; ``routed_scaling_factor`` null = 1); no
shared expert; only the experts ``first .. first + count - 1`` are held and
summed (the guide's section 4: all scored, ``top_k`` taken, the held ones
add). Final RMSNorm, untied head.

So that 9,728 tokens fit: a sequence at a time through the layers, and
attention a block of ``QUERY_BLOCK`` queries at a time (against every key on
a full layer; on a sliding one against the ``window`` keys before the block
and the block's own, which are all it can see), so that no ``[heads, S, S]``
score array ever exists.

``quant`` is the control of "How correct is decided", parts joined by ``+``:
``"fp8"`` rounds both operands of every linear layer (router and head among
them) to float8 e4m3 with one scale a tensor, the step below bfloat16;
``"bf16"`` rounds them to bfloat16 (no control: the program's own precision);
``"no_sink"`` leaves the ``e^{b_h - m}`` term out of every sliding layer;
``"no_vscale"`` leaves ``attention_value_scale`` out.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from .gpt_dense import HI, _Static
from .kimi_linear import _has, _linear_quant, mm, routed
from .laguna_swa import gated_mlp, rms_norm, rotate

F32 = jnp.float32
FULL, SLIDING = "full", "sliding"
QUERY_BLOCK = 256


def attention(u, lp, kind: str, d, quant=None):
    """``u`` [S, H] -> the attention branch's output [S, H]."""
    lq = _linear_quant(quant)
    s = u.shape[0]
    p = "full_" if kind == FULL else "swa_"
    heads, kvh, hd, vd = (d[p + k] for k in ("heads", "kv", "hd", "vd"))
    grp = heads // kvh
    window = d["window"] if kind == SLIDING else None
    qkv = mm(u, lp["attn.qkv_proj.weight"], lq)
    q, k, v = jnp.split(qkv, [heads * hd, (heads + kvh) * hd], -1)
    if not _has(quant, "no_vscale"):
        v = v * d["vscale"]
    inv = d[p + "theta"] ** (
        -jnp.arange(0, d[p + "rot"], 2, dtype=F32) / d[p + "rot"])
    q = rotate(q.reshape(s, heads, hd), inv)
    k = rotate(k.reshape(s, kvh, hd), inv)
    v = v.reshape(s, kvh, vd)
    sink = None
    if d[p + "sink"] and not _has(quant, "no_sink"):
        sink = lp["attn.sinks"].reshape(kvh, grp)      # head h = [h // grp]
    blk = min(s, QUERY_BLOCK)
    if s % blk:
        raise ValueError(f"sequence length {s} is no multiple of {blk}")
    if window is not None and s > blk + window:
        # the keys a block can see: `window` before it and its own
        k = jnp.concatenate([jnp.zeros((window, kvh, hd), F32), k])
        v = jnp.concatenate([jnp.zeros((window, kvh, vd), F32), v])
        span = blk + window
    else:
        span = None

    def block_of_queries(xs):
        qb, first = xs                           # [blk, kvh, grp, hd]
        if span is None:
            kb, vb, keys = k, v, jnp.arange(s)
        else:
            kb = jax.lax.dynamic_slice_in_dim(k, first, span)
            vb = jax.lax.dynamic_slice_in_dim(v, first, span)
            keys = first - window + jnp.arange(span)
        back = (first + jnp.arange(blk))[:, None] - keys[None, :]
        seen = (back >= 0) & (keys >= 0)[None, :]
        if window is not None:
            seen = seen & (back < window)
        sc = jnp.einsum("qkgd,skd->kgqs", qb, kb, precision=HI) \
            / math.sqrt(hd)
        sc = jnp.where(seen, sc, -jnp.inf)
        m = sc.max(-1, keepdims=True)
        if sink is not None:
            m = jnp.maximum(m, sink[:, :, None, None])
        e = jnp.exp(sc - m)
        total = e.sum(-1, keepdims=True)
        if sink is not None:
            total = total + jnp.exp(sink[:, :, None, None] - m)
        return jnp.einsum("kgqs,skd->qkgd", e / total, vb, precision=HI)

    o = jax.lax.map(block_of_queries,
                    (q.reshape(s // blk, blk, kvh, grp, hd),
                     jnp.arange(s // blk) * blk))
    return mm(o.reshape(s, heads * vd), lp["attn.o_proj.weight"], lq)


def block(x, lp, kind: str, dense: bool, d, quant=None):
    """One layer. ``x`` [S, H] float32; ``lp`` the layer's leaves (any float
    type; the stacked experts are upcast one at a time)."""
    lp = {k: (v if k in ("moe.w_in", "moe.w_out") else v.astype(F32))
          for k, v in lp.items()}
    lq = _linear_quant(quant)
    u = rms_norm(x, lp["input_norm.weight"], d["eps"])
    x = x + attention(u, lp, kind, d, quant)
    u = rms_norm(x, lp["post_norm.weight"], d["eps"])
    if dense:
        return x + gated_mlp(u, lp["mlp.w_in.weight"],
                             lp["mlp.w_out.weight"], lq)
    return x + routed(u, lp, d, lq)


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


def served_gaps(params: dict, ids, first, count, served, d, quants=()):
    """As ``gpt_dense.served_gaps``: for each row of ``ids`` [B, S] (prompt
    then served tokens, padded) and each served position ``first[b] <= t <
    first[b] + count[b]``, how far the logit of the served token lies below
    the reference's best (``gap``, with ``mask``); and for each control in
    ``quants`` the gap of the token THAT control puts first
    (``control_gap[name]``)."""
    dd = _sizes(d)
    top, x = hidden_by_layer(params, ids, d, None)
    xq = {}
    for q in quants:
        # a loaded program keeps its workspace (1-2 GB a shape of layer at
        # 9,728 tokens) beside 9.85 GB of weights: let the pass before go
        jax.clear_caches()
        xq[q] = hidden_by_layer(params, ids, d, q)[1]

    def row(top, a):
        xr, xqr, sv, f, c = a
        lg = head(top, xr, dd)
        best = lg.max(-1)
        t = jnp.arange(lg.shape[0])
        mask = (t >= f) & (t < f + c)

        def below_best(tok):
            return jnp.where(mask, best - jnp.take_along_axis(
                lg, tok[:, None], -1)[:, 0], 0.0)

        return {"gap": below_best(sv), "mask": mask,
                "control_gap": {q: below_best(
                    jnp.argmax(head(top, xqr[q], dd, q), -1))
                    for q in quants}}

    # one row at a time, so that no [B, S, V] array exists
    return jax.jit(lambda top, xs: jax.lax.map(
        lambda a: row(top, a), xs))(top, (x, xq, served, first, count))
