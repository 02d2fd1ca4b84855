"""Olmo Hybrid (``model_type: olmo_hybrid``, config.json of
allenai/Olmo-Hybrid-7B) in plain ``jax.numpy``: float32,
``precision="highest"`` on every matrix product, no kernels, no cache, no
pages, no chunk form. It imports nothing of the program. It reads the weights
the benchmark made (``benchmark/weights_olmo.py``), upcast one layer at a
time.

``x = E[ids]`` (float32, no biases anywhere, eps ``rms_norm_eps``); layer
``l`` of ``kinds[l]`` (``layer_types``), ``h`` one of the heads::

    linear_attention (a gated delta rule, Yang et al. arXiv:2412.06464, as
    the linear_* keys lay it out; K = linear_key_head_dim, V =
    linear_value_head_dim):
      [q | k | v] = silu(conv(x W_qkv))   depthwise causal conv over the
                                          last `conv` positions, no bias
      q_h = l2norm(q_h) / sqrt(K)   k_h = l2norm(k_h)
      b_h = 2 sigmoid(x W_b)_h      in (0, 2)  (linear_allow_neg_eigval)
      log a_h = -exp(A_log_h) softplus((x W_a)_h + dt_bias_h)   ONE a head
      S_t = a_t (I - b_t k_t k_t^T) S_{t-1} + b_t k_t v_t^T     S in R^{K x V}
      o_t = S_t^T q_t                     TOKEN BY TOKEN (a lax.scan)
      m   = concat_h(RMSNorm_V(o_h; w) * silu((x W_g)_h)) W_o
    full_attention:
      q = RMSNorm(x W_q)   k = RMSNorm(x W_k)   v = x W_v   the norms over
      the WHOLE projection, then heads of head_dim; no rotation (rope_theta
      null); s_ij = q_i . k_j / sqrt(head_dim), j <= i;
      m = concat_h(softmax_j(s) v) W_o
    x = x + RMSNorm(m(x));   x = x + RMSNorm(W_down(silu(W_gate x) * W_up x))

Final RMSNorm, untied head.

Departures from the published description: the fused ``W_qkv`` of both
mixers and the fused gate / up matrix of the SwiGLU (the program's leaves;
the same numbers as separate matrices); everything in the configuration's
``assumed``.

So that 3,584 tokens fit: a sequence at a time through the layers, the
attention scores a block of ``QUERY_BLOCK`` queries at a time against every
key, so that no ``[heads, S, S]`` array ever exists; the rule carries one
``[heads, K, V]`` state through a scan over the tokens.

``quant`` is the control of "How correct is decided": ``"fp8"`` rounds both
operands of every linear layer (gates and head among them) to float8 e4m3
with one scale a tensor, the step below bfloat16; ``"bf16"`` rounds them to
bfloat16, the program's own precision (no control: the floor the program's
gap is read against). Three of the MODEL, not of precision, each a fault a
program could have: ``"beta_unscaled"`` (``b = sigmoid(.)``, the factor 2
left out), ``"no_qk_norm"`` (the full layers' two norms left out) and, read
and reported but not required to fail, ``"state_bf16"`` (the rule's state
rounded to bfloat16 after every token). A ``+`` joins them
(``"bf16+state_bf16"``).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from .gpt_dense import HI, _Static
# the other delta-rule reference's plain pieces (norms, the quantised product,
# the causal convolution, the SwiGLU): the same functions, not a second copy
from .kimi_linear import (_as_bf16, _has, _linear_quant, causal_conv,
                          gated_mlp, l2norm, mm, rms_norm, silu)

F32 = jnp.float32
QUERY_BLOCK = 512
LINEAR, FULL = "linear_attention", "full_attention"


def delta_rule(q, k, v, log_a, b, round_state=False):
    """The recurrence, token by token, from a zero state: ``q``, ``k`` [S,
    heads, K], ``v`` [S, heads, V], ``log_a``, ``b`` [S, heads]: ONE decay
    and one write strength a head."""
    def step(s, xs):
        qt, kt, vt, at, bt = xs
        s = jnp.exp(at)[:, None, None] * s
        s = s + (bt[:, None] * kt)[..., None] * (
            vt - jnp.einsum("hk,hkv->hv", kt, s, precision=HI))[:, None, :]
        if round_state:
            s = _as_bf16(s)
        return s, jnp.einsum("hk,hkv->hv", qt, s, precision=HI)

    h, dk = q.shape[1:]
    _, o = jax.lax.scan(step, jnp.zeros((h, dk, v.shape[-1]), F32),
                        (q, k, v, log_a, b))
    return o


def linear_attention(x, lp, d, quant=None):
    """``x`` [S, H] -> the rule's branch [S, H], before the branch's norm."""
    lq = _linear_quant(quant)
    s = x.shape[0]
    nh, dk, dv = d["lin_heads"], d["lin_k"], d["lin_v"]
    qkv = silu(causal_conv(mm(x, lp["mixer.qkv_proj.weight"], lq),
                           lp["mixer.conv_weight"]))
    q, k, v = jnp.split(qkv, [nh * dk, 2 * nh * dk], -1)
    q = l2norm(q.reshape(s, nh, dk)) / math.sqrt(dk)
    k = l2norm(k.reshape(s, nh, dk))
    v = v.reshape(s, nh, dv)
    log_a = -jnp.exp(lp["mixer.A_log"]) * jax.nn.softplus(
        mm(x, lp["mixer.a_proj.weight"], lq) + lp["mixer.dt_bias"])
    b = jax.nn.sigmoid(mm(x, lp["mixer.b_proj.weight"], lq))
    if d["neg_eigval"] and not _has(quant, "beta_unscaled"):
        b = 2.0 * b
    o = delta_rule(q, k, v, log_a, b, _has(quant, "state_bf16"))
    gate = silu(mm(x, lp["mixer.g_proj.weight"], lq))
    o = rms_norm(o, lp["mixer.o_norm_weight"], d["eps"])
    return mm(o.reshape(s, nh * dv) * gate, lp["mixer.o_proj.weight"], lq)


def full_attention(x, lp, d, quant=None):
    """``x`` [S, H] -> the attention branch [S, H], a block of queries at a
    time against every key."""
    lq = _linear_quant(quant)
    s = x.shape[0]
    n, nkv, hd = d["heads"], d["kv_heads"], d["hd"]
    q, k, v = jnp.split(mm(x, lp["mixer.qkv_proj.weight"], lq),
                        [n * hd, (n + nkv) * hd], -1)
    if not _has(quant, "no_qk_norm"):
        q = rms_norm(q, lp["mixer.q_norm.weight"], d["eps"])
        k = rms_norm(k, lp["mixer.k_norm.weight"], d["eps"])
    q = q.reshape(s, n, hd)
    k, v = (jnp.repeat(t.reshape(s, nkv, hd), n // nkv, axis=1)
            for t in (k, v))
    blk = min(s, QUERY_BLOCK)
    if s % blk:
        raise ValueError(f"sequence length {s} is no multiple of {blk}")

    def block_of_queries(xs):
        qb, first = xs                                       # [blk, n, hd]
        seen = (first + jnp.arange(blk))[:, None] >= jnp.arange(s)[None, :]
        sc = jnp.einsum("qhd,khd->hqk", qb, k, precision=HI) / math.sqrt(hd)
        p = jax.nn.softmax(jnp.where(seen, sc, -jnp.inf), -1)
        return jnp.einsum("hqk,khd->qhd", p, v, precision=HI)

    o = jax.lax.map(block_of_queries,
                    (q.reshape(s // blk, blk, n, hd),
                     jnp.arange(s // blk) * blk))
    return mm(o.reshape(s, n * hd), lp["mixer.o_proj.weight"], lq)


def block(x, lp, kind: str, d, quant=None):
    """One layer. ``x`` [S, H] float32; ``lp`` the layer's leaves (any float
    type). No norm before a branch, one on its output."""
    lp = {k: v.astype(F32) for k, v in lp.items()}
    mixer = linear_attention if kind == LINEAR else full_attention
    x = x + rms_norm(mixer(x, lp, d, quant), lp["mixer_norm.weight"],
                     d["eps"])
    return x + rms_norm(
        gated_mlp(x, lp["mlp.w_in.weight"], lp["mlp.w_out.weight"],
                  _linear_quant(quant)), lp["mlp_norm.weight"], d["eps"])


def head(top, x, d, quant=None):
    y = rms_norm(x, top["final_norm.weight"].astype(F32), d["eps"])
    return mm(y, top["lm_head.weight"].astype(F32), _linear_quant(quant))


def _sizes(d) -> _Static:
    """The sizes without the per-layer tuple: hashable, so static."""
    return _Static({k: v for k, v in d.items() if k != "kinds"})


def hidden_by_layer(params: dict, ids, d, quant=None):
    """Final hidden states [B, S, H] of ``ids`` [B, S]: a sequence at a
    time, a layer at a time through one compiled block a kind of layer."""
    dd = _sizes(d)
    top = {k: v for k, v in params.items() if not k.startswith("layers.")}
    step = jax.jit(block, static_argnums=(2, 3, 4))
    layers = []
    for l in range(d["L"]):
        pre = f"layers.{l}."
        layers.append({k[len(pre):]: v for k, v in params.items()
                       if k.startswith(pre)})
    rows = []
    for row in ids:
        x = top["embed.weight"][jnp.asarray(row)].astype(F32)
        for l, lp in enumerate(layers):
            x = step(x, lp, d["kinds"][l], dd, quant)
        rows.append(x)
    return top, jnp.stack(rows)


def logits(params: dict, ids, d, quant=None):
    top, x = hidden_by_layer(params, ids, d, quant)
    return head(top, x, _sizes(d), quant)


def served_gaps(params: dict, ids, first, count, served, d, quants=()):
    """As ``reference/kimi_linear.served_gaps``: for each row of ``ids`` [B,
    S] (prompt then served tokens, padded) and each served position
    ``first[b] <= t < first[b] + count[b]``, how far the logit of the served
    token lies below the reference's best (``gap``, with ``mask``); and for
    each control in ``quants`` the gap of the token THAT control puts first
    (``control_gap[name]``)."""
    dd = _sizes(d)
    top, x = hidden_by_layer(params, ids, d, None)
    xq = {}
    for q in quants:
        # a loaded program keeps its workspace beside the weights: let the
        # pass before go
        jax.clear_caches()
        xq[q] = hidden_by_layer(params, ids, d, q)[1]

    b, n = ids.shape
    blk = min(n, QUERY_BLOCK)
    nb = n // blk

    def piece(top, a):
        xr, xqr, sv, t0, f, c = a              # a block of one row's positions
        lg = head(top, xr, dd)
        best = lg.max(-1)
        t = t0 + jnp.arange(blk)
        mask = (t >= f) & (t < f + c)

        def below_best(tok):
            return jnp.where(mask, best - jnp.take_along_axis(
                lg, tok[:, None], -1)[:, 0], 0.0)

        return {"gap": below_best(sv), "mask": mask,
                "control_gap": {q: below_best(
                    jnp.argmax(head(top, xqr[q], dd, q), -1))
                    for q in quants}}

    def blocks(a):
        return a.reshape((b * nb, blk) + a.shape[2:])

    # a block of positions at a time, so that no [S, V] array exists (the
    # whole vocabulary: 1.4 GB a row of 3,584 positions, once a control)
    out = jax.jit(lambda top, xs: jax.lax.map(
        lambda a: piece(top, a), xs))(
            top, (blocks(x), {q: blocks(v) for q, v in xq.items()},
                  blocks(jnp.asarray(served)),
                  jnp.tile(jnp.arange(nb) * blk, b),
                  jnp.repeat(jnp.asarray(first), nb),
                  jnp.repeat(jnp.asarray(count), nb)))
    return jax.tree_util.tree_map(lambda a: a.reshape(b, n), out)
