"""Seeded weights for the gated-delta-rule / full-attention configuration
(Olmo Hybrid: three ``linear_attention`` mixers with one decay a head, then a
``full_attention`` mixer with Q/K norms over the whole projection, a SwiGLU
after each), made by the benchmark on the device and handed to the program
and to the plain reference alike, under the program's leaf names
(``paddle_tpu/models/olmo_hybrid.py``).

One jitted call a layer (a program a kind of layer) and one for the top: the
random bits of eight layers and the whole vocabulary at once would not fit
beside the 4.87 GB they make. The same seed gives the same arrays.

Distribution (``assumed`` in the configuration file): every matrix normal,
std 0.02 (the embedding, the untied head and the convolution among them);
norms at one; ``A_log`` = log of uniform(1, 16); ``dt_bias`` the inverse
softplus of a step log-uniform in (0.001, 0.1). ``A_log`` and ``dt_bias``
are float32 whatever the weights' type.
"""

from __future__ import annotations

import math

from .weights import STD, key_words

LINEAR, FULL = "linear_attention", "full_attention"


def dims_of(cfg: dict) -> dict:
    """The sizes the generators, the reference and the roofline need, from a
    configuration file (published keys at its top level; ``num_layers`` is
    what is built here of the published ``num_hidden_layers``)."""
    n = int(cfg.get("num_layers", cfg["num_hidden_layers"]))
    kinds = tuple(cfg["layer_types"][:n])
    if len(kinds) < n or any(k not in (LINEAR, FULL) for k in kinds):
        raise ValueError("layer_types names no kind for some layer")
    if (cfg.get("rope_parameters") or {}).get("rope_theta") is not None:
        raise ValueError("rope_theta is published null: no rotation")
    if cfg["linear_num_key_heads"] != cfg["linear_num_value_heads"]:
        raise ValueError("a key head a value head is what is published")
    h, heads = int(cfg["hidden_size"]), int(cfg["num_attention_heads"])
    return {
        "kinds": kinds, "L": n, "H": h, "V": int(cfg["vocab_size"]),
        "F": int(cfg["intermediate_size"]),
        "heads": heads, "kv_heads": int(cfg["num_key_value_heads"]),
        "hd": h // heads,
        "lin_heads": int(cfg["linear_num_value_heads"]),
        "lin_k": int(cfg["linear_key_head_dim"]),
        "lin_v": int(cfg["linear_value_head_dim"]),
        "conv": int(cfg["linear_conv_kernel_dim"]),
        "neg_eigval": bool(cfg["linear_allow_neg_eigval"]),
        "eps": float(cfg["rms_norm_eps"]),
    }


def conv_width(d: dict) -> int:
    """The channels of the one convolution over ``[q | k | v]``."""
    return d["lin_heads"] * (2 * d["lin_k"] + d["lin_v"])


def layer_leaves(d: dict, l: int) -> list:
    """``(leaf, shape, distribution)`` of layer ``l``."""
    h = d["H"]
    if d["kinds"][l] == LINEAR:
        nh, inner = d["lin_heads"], d["lin_heads"] * d["lin_v"]
        leaves = [
            ("mixer.qkv_proj.weight", (h, conv_width(d)), "normal"),
            ("mixer.conv_weight", (d["conv"], conv_width(d)), "normal"),
            ("mixer.a_proj.weight", (h, nh), "normal"),
            ("mixer.dt_bias", (nh,), "dt_bias"),
            ("mixer.A_log", (nh,), "a_log"),
            ("mixer.b_proj.weight", (h, nh), "normal"),
            ("mixer.g_proj.weight", (h, inner), "normal"),
            ("mixer.o_norm_weight", (d["lin_v"],), "one"),
            ("mixer.o_proj.weight", (inner, h), "normal"),
        ]
    else:
        q, kv = d["heads"] * d["hd"], d["kv_heads"] * d["hd"]
        leaves = [
            ("mixer.qkv_proj.weight", (h, q + 2 * kv), "normal"),
            ("mixer.q_norm.weight", (q,), "one"),
            ("mixer.k_norm.weight", (kv,), "one"),
            ("mixer.o_proj.weight", (q, h), "normal"),
        ]
    return leaves + [
        ("mixer_norm.weight", (h,), "one"),
        ("mlp.w_in.weight", (h, 2 * d["F"]), "normal"),
        ("mlp.w_out.weight", (d["F"], h), "normal"),
        ("mlp_norm.weight", (h,), "one")]


def top_leaves(d: dict) -> list:
    h, v = d["H"], d["V"]
    return [("embed.weight", (v, h), "normal"),
            ("final_norm.weight", (h,), "one"),
            ("lm_head.weight", (h, v), "normal")]


def n_params(d: dict) -> int:
    return sum(math.prod(s) for _, s, _ in top_leaves(d)) + sum(
        math.prod(s) for l in range(d["L"])
        for _, s, _ in layer_leaves(d, l))


def make(d: dict, seed: int, dtype) -> dict:
    """``{program leaf name: array}`` on the default device."""
    import jax
    import jax.numpy as jnp
    from functools import partial

    def draw(key, shape, dist):
        f32 = jnp.float32
        if dist == "normal":
            return (jax.random.normal(key, shape, f32) * STD).astype(dtype)
        if dist == "one":
            return jnp.ones(shape, dtype)
        if dist == "a_log":
            return jnp.log(jax.random.uniform(key, shape, f32, 1.0, 16.0))
        if dist == "dt_bias":
            dt = jnp.exp(jax.random.uniform(
                key, shape, f32, math.log(1e-3), math.log(1e-1)))
            return dt + jnp.log(-jnp.expm1(-dt))
        raise ValueError(dist)

    @partial(jax.jit, static_argnums=(2,))
    def build(words, index, leaves):
        base = jax.random.fold_in(jax.random.wrap_key_data(
            jnp.asarray(words, jnp.uint32), impl="rbg"), index)
        return {name: draw(jax.random.fold_in(base, i), shape, dist)
                for i, (name, shape, dist) in enumerate(leaves)}

    words = key_words(seed)
    out = dict(build(words, 0, tuple(top_leaves(d))))
    for l in range(d["L"]):
        made = build(words, l + 1, tuple(layer_leaves(d, l)))
        out.update({f"layers.{l}.{n}": v for n, v in made.items()})
    return out
