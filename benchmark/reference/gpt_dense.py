"""GPT-3 (Brown et al. 2020, arXiv:2005.14165) in plain ``jax.numpy``.

Pre-LN decoder blocks, learned positions, exact (erf) GELU, tied output head,
float32 with ``precision="highest"`` on every matrix product: no kernels, no
cache, no batching tricks. It imports nothing of the program. It reads the
weights the benchmark made (``benchmark/weights.py``), upcast leaf by leaf.

``quant`` puts the reference in the program's place at a lower precision (the
control of "How correct is decided"): ``"fp8"`` rounds both operands of every
linear layer and of the output head to float8 e4m3 with one scale a tensor,
the step below bfloat16; ``"bf16"`` rounds them to bfloat16. Products still
accumulate in float32.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
HYPER = ("lr", "beta1", "beta2", "epsilon", "weight_decay")


def _q(x, quant):
    """Round ``x`` as the lower precision would hold it. The gradient passes
    straight through, so a control trains with rounded operands and unrounded
    gradients."""
    if quant is None:
        return x
    if quant == "bf16":
        r = x.astype(jnp.bfloat16).astype(jnp.float32)
    elif quant == "fp8":
        s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
        r = (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s
    else:
        raise ValueError(f"unknown quant {quant!r}")
    return x + jax.lax.stop_gradient(r - x)


def mm(a, b, quant=None):
    return jnp.matmul(_q(a, quant), _q(b, quant), precision=HI)


def layer_norm(x, w, b, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * w + b


def gelu(x):
    return 0.5 * x * (1.0 + jax.lax.erf(x / math.sqrt(2.0)))


def block(x, lp, heads: int, eps: float, quant=None):
    """One decoder block. ``x`` [B, S, H] float32; ``lp`` one layer's leaves
    (any float type), keyed as in ``weights._LAYER_LEAVES``."""
    lp = {k: v.astype(jnp.float32) for k, v in lp.items()}
    b, s, h = x.shape
    hd = h // heads
    y = layer_norm(x, lp["ln_1.weight"], lp["ln_1.bias"], eps)
    qkv = mm(y, lp["attn.qkv_proj.weight"], quant) + lp["attn.qkv_proj.bias"]
    q, k, v = (t.reshape(b, s, heads, hd) for t in jnp.split(qkv, 3, -1))
    sc = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=HI) / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((s, s), bool))
    p = jax.nn.softmax(jnp.where(causal, sc, -jnp.inf), -1)
    a = jnp.einsum("bhqk,bkhd->bqhd", p, v, precision=HI).reshape(b, s, h)
    x = x + mm(a, lp["attn.out_proj.weight"], quant) \
        + lp["attn.out_proj.bias"]
    y = layer_norm(x, lp["ln_2.weight"], lp["ln_2.bias"], eps)
    y = gelu(mm(y, lp["mlp.fc_in.weight"], quant) + lp["mlp.fc_in.bias"])
    return x + mm(y, lp["mlp.fc_out.weight"], quant) + lp["mlp.fc_out.bias"]


def embed(top, ids):
    wte = top["gpt.embeddings.word_embeddings.weight"]
    wpe = top["gpt.embeddings.position_embeddings.weight"]
    s = ids.shape[-1]
    return wte[ids].astype(jnp.float32) + wpe[:s].astype(jnp.float32)


def head(top, x, eps: float, quant=None):
    """Final norm and the tied output head: logits [..., V]."""
    y = layer_norm(x, top["gpt.ln_f.weight"].astype(jnp.float32),
                   top["gpt.ln_f.bias"].astype(jnp.float32), eps)
    wte = top["gpt.embeddings.word_embeddings.weight"].astype(jnp.float32)
    return mm(y, wte.T, quant)


# -- serving: teacher-forced logits, one layer at a time ----------------------

def hidden_by_layer(params: dict, ids, d: dict, quant=None):
    """Final hidden states [B, S, H] for ``ids`` [B, S], reading the
    per-layer leaves ``gpt.layers.<l>.<leaf>`` one layer at a time through one
    compiled block."""
    top = {k: v for k, v in params.items() if ".layers." not in k}
    x = jax.jit(embed)(top, ids)
    step = jax.jit(block, static_argnums=(2, 3, 4))
    for l in range(d["L"]):
        pre = f"gpt.layers.{l}."
        lp = {k[len(pre):]: v for k, v in params.items()
              if k.startswith(pre)}
        x = step(x, lp, d["heads"], d["eps"], quant)
    return top, x


def served_gaps(params: dict, ids, first, count, served, d: dict,
                quant=None):
    """For each row of ``ids`` [B, S] (prompt then served tokens, padded), and
    each served position ``first[b] <= t < first[b] + count[b]`` (the logits
    at ``t`` choose token ``t + 1``): how far the logit of the served token
    ``served[b, t]`` lies below the reference's best. Returns arrays [B, S]
    ``gap`` (0 where not served), ``mask``; with ``quant``, also the gap of
    the token the lower precision puts first (the control's reading)."""
    top, x = hidden_by_layer(params, ids, d, None)
    xq = hidden_by_layer(params, ids, d, quant)[1] if quant else None

    def row(top, xr, xqr, sv, f, c):
        lg = head(top, xr, d["eps"])
        best = lg.max(-1)
        t = jnp.arange(lg.shape[0])
        mask = (t >= f) & (t < f + c)

        def below_best(tok):
            return jnp.where(mask, best - jnp.take_along_axis(
                lg, tok[:, None], -1)[:, 0], 0.0)

        out = {"gap": below_best(sv), "mask": mask}
        if xqr is not None:
            out["control_gap"] = below_best(
                jnp.argmax(head(top, xqr, d["eps"], quant), -1))
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


# -- training: loss, gradients and AdamW over stacked layers -------------------

def loss_stacked(p: dict, ids, d: dict, quant=None):
    """Mean next-token cross entropy of ``ids`` [B, S]; layer leaves stacked
    ``layers.<leaf>`` [L, ...]; each block rematerialised in the backward
    pass so that a chip holds it."""
    x = embed(p, ids)
    stack = {k[len("layers."):]: v for k, v in p.items()
             if k.startswith("layers.")}

    @jax.checkpoint
    def body(x, lp):
        return block(x, lp, d["heads"], d["eps"], quant), None

    x, _ = jax.lax.scan(body, x, stack)
    lg = head(p, x[:, :-1], d["eps"], quant)
    lp = jax.nn.log_softmax(lg, -1)
    tgt = ids[:, 1:]
    return -jnp.mean(jnp.take_along_axis(lp, tgt[..., None], -1))


def leaf_norms(tree: dict) -> dict:
    """L2 norm of each leaf; of each layer's slice for stacked leaves."""
    out = {}
    for k, v in tree.items():
        v = v.astype(jnp.float32)
        if k.startswith("layers."):
            out[k] = jnp.sqrt(jnp.sum(jnp.square(v).reshape(v.shape[0], -1),
                                      -1))
        else:
            out[k] = jnp.sqrt(jnp.sum(jnp.square(v)))
    return out


def adamw_step(p, m, v, t, ids, d, hp, quant=None):
    """One plain AdamW step (decoupled decay on every leaf, bias-corrected,
    epsilon outside the root). Returns new (p, m, v), the loss and the
    per-leaf gradient norms."""
    loss, g = jax.value_and_grad(loss_stacked)(p, ids, d, quant)
    b1, b2 = hp["beta1"], hp["beta2"]
    bc1, bc2 = 1 - b1 ** t, 1 - b2 ** t
    new_p, new_m, new_v = {}, {}, {}
    for k in p:
        new_m[k] = b1 * m[k] + (1 - b1) * g[k]
        new_v[k] = b2 * v[k] + (1 - b2) * jnp.square(g[k])
        step = (new_m[k] / bc1) / (jnp.sqrt(new_v[k] / bc2) + hp["epsilon"])
        new_p[k] = p[k] - hp["lr"] * step - hp["lr"] * hp["weight_decay"] * p[k]
    return new_p, new_m, new_v, loss, leaf_norms(g)


def train_reference(make_p0, batches, d: dict, hp: dict, quant=None):
    """Follow the first ``len(batches)`` steps from ``make_p0()`` (traceable;
    stacked float32 leaves). Returns the losses, the first step's per-leaf
    gradient norms and the per-leaf norms of the parameters' change after the
    last step. Holds p, m and v only: the steps donate them."""
    dd = _Static({k: d[k] for k in ("heads", "eps")})
    hp = _Static({k: float(hp[k]) for k in HYPER})
    step = jax.jit(adamw_step, static_argnums=(5, 6, 7),
                   donate_argnums=(0, 1, 2))
    p = jax.jit(make_p0)()
    zeros = jax.jit(lambda t: jax.tree_util.tree_map(jnp.zeros_like, t))
    m, v = zeros(p), zeros(p)
    losses, gnorm1 = [], None
    for i, ids in enumerate(batches):
        p, m, v, loss, gn = step(p, m, v, jnp.float32(i + 1), ids, dd, hp,
                                 quant)
        losses.append(float(loss))
        if i == 0:
            gnorm1 = jax.device_get(gn)
    del m, v
    change = jax.device_get(jax.jit(lambda a: leaf_norms(
        {k: a[k] - b for k, b in make_p0().items()}))(p))
    return {"losses": losses, "grad_norms": gnorm1, "change_norms": change}


class _Static(dict):
    """A hashable dict, so that sizes and hyperparameters can be static."""

    def __hash__(self):
        return hash(tuple(sorted(self.items())))
