"""Seeded weights for the window / full attention configuration (Laguna:
per-layer head counts, a per-head output gate, a leading dense layer, then
routed + shared experts), made by the benchmark on the device and handed to
the program and to the plain reference alike, under the program's leaf names
(``paddle_tpu/models/laguna.py``).

One jitted call a layer (a program a shape of layer: the dense one, a routed
full one, a routed sliding one) and one for the top: a routed layer's largest
leaf is 201M values, and the random bits of twelve layers at once would not
fit beside the 8.65 GB they make. The same seed gives the same arrays.

Distribution (``assumed`` in the configuration file): every matrix normal,
std 0.02 (the embedding and the untied head among them); norms at one.
"""

from __future__ import annotations

import math

from .weights import STD, key_words

FULL, SLIDING = "full_attention", "sliding_attention"


def dims_of(cfg: dict) -> dict:
    """The sizes the generators, the reference and the roofline need, from a
    configuration file (published keys at its top level; ``num_layers``
    (``num_hidden_layers`` where the file has no cut of depth),
    ``num_experts`` and ``vocab_size`` are what is held here, ``published``
    what the source has)."""
    n = int(cfg.get("num_layers", cfg["num_hidden_layers"]))
    published = cfg.get("published", {})
    first, count = cfg.get("experts_held", (0, int(cfg["num_experts"])))
    rope = cfg["rope_parameters"]
    full, sliding = rope[FULL], rope[SLIDING]
    hd = int(cfg["head_dim"])
    return {
        "kinds": tuple(cfg["layer_types"][:n]),
        "heads": tuple(int(h) for h in
                       cfg["num_attention_heads_per_layer"][:n]),
        "dense": tuple(int(i) for i in cfg["mlp_only_layers"] if i < n),
        "L": n, "H": int(cfg["hidden_size"]), "V": int(cfg["vocab_size"]),
        "kv_heads": int(cfg["num_key_value_heads"]), "hd": hd,
        "window": int(cfg["sliding_window"]),
        "F": int(cfg["intermediate_size"]),
        "E": int(published.get("num_experts", cfg["num_experts"])),
        "first": int(first), "count": int(count),
        "top_k": int(cfg["num_experts_per_tok"]),
        "de": int(cfg["moe_intermediate_size"]),
        "ds": int(cfg["shared_expert_intermediate_size"]),
        "routed_scale": float(cfg["moe_routed_scaling_factor"]),
        "eps": float(cfg["rms_norm_eps"]),
        "sliding_theta": float(sliding["rope_theta"]),
        "sliding_rot": int(hd * sliding["partial_rotary_factor"]),
        "full_theta": float(full["rope_theta"]),
        "full_rot": int(hd * full["partial_rotary_factor"]),
        "yarn_factor": float(full["factor"]),
        "yarn_original": int(full["original_max_position_embeddings"]),
        "yarn_beta_fast": float(full["beta_fast"]),
        "yarn_beta_slow": float(full["beta_slow"]),
        "attention_factor": float(full["attention_factor"]),
    }


def layer_leaves(d: dict, l: int) -> list:
    """``(leaf, shape, distribution)`` of layer ``l``."""
    h, kv = d["H"], d["kv_heads"] * d["hd"]
    n = d["heads"][l]
    leaves = [
        ("input_norm.weight", (h,), "one"),
        ("attn.qkv_proj.weight", (h, n * d["hd"] + 2 * kv), "normal"),
        ("attn.g_proj.weight", (h, n), "normal"),
        ("attn.o_proj.weight", (n * d["hd"], h), "normal"),
        ("post_norm.weight", (h,), "one"),
    ]
    if l in d["dense"]:
        return leaves + [
            ("mlp.w_in.weight", (h, 2 * d["F"]), "normal"),
            ("mlp.w_out.weight", (d["F"], h), "normal")]
    return leaves + [
        ("moe.router", (h, d["E"]), "normal"),
        ("moe.w_in", (d["count"], h, 2 * d["de"]), "normal"),
        ("moe.w_out", (d["count"], d["de"], h), "normal"),
        ("shared.w_in.weight", (h, 2 * d["ds"]), "normal"),
        ("shared.w_out.weight", (d["ds"], h), "normal")]


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

    @partial(jax.jit, static_argnums=(2,))
    def build(words, index, leaves):
        base = jax.random.fold_in(jax.random.wrap_key_data(
            jnp.asarray(words, jnp.uint32), impl="rbg"), index)
        return {name: (jax.random.normal(jax.random.fold_in(base, i), shape,
                                         jnp.float32) * STD).astype(dtype)
                if dist == "normal" else jnp.ones(shape, dtype)
                for i, (name, shape, dist) in enumerate(leaves)}

    words = key_words(seed)
    out = dict(build(words, 0, tuple(top_leaves(d))))
    for l in range(d["L"]):
        made = build(words, l + 1, tuple(layer_leaves(d, l)))
        out.update({f"layers.{l}.{n}": v for n, v in made.items()})
    return out
