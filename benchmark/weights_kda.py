"""Seeded weights for the delta-rule / latent-attention configuration (Kimi
Linear: KDA mixers with a decay a channel, an MLA mixer every fourth layer,
a leading dense layer, then sigmoid-routed + shared experts), made by the
benchmark on the device and handed to the program and to the plain reference
alike, under the program's leaf names (``paddle_tpu/models/kimi_linear.py``).

One jitted call a layer (a program a shape of layer: the dense KDA one, a
routed KDA one, a routed MLA one) and one for the top: the random bits of
twenty-seven layers at once would not fit beside the 8.6 GB they make. The
same seed gives the same arrays.

Distribution (``assumed`` in the configuration file): every matrix normal,
std 0.02 (the embedding, the untied head and the convolution among them);
norms at one, the gate's bias at zero; ``e_bias`` normal std 0.01; ``A_log``
= log of uniform(1, 16); ``dt_bias`` the inverse softplus of a step
log-uniform in (0.001, 0.1). ``e_bias``, ``A_log``, ``dt_bias`` and the
gate's bias are float32 whatever the weights' type.
"""

from __future__ import annotations

import math

from .weights import STD, key_words


def dims_of(cfg: dict) -> dict:
    """The sizes the generators, the reference and the roofline need, from a
    configuration file (published keys at its top level; ``num_layers``
    (``num_hidden_layers`` where the file has no cut of depth),
    ``num_experts`` and ``vocab_size`` are what is held here, ``published``
    what the source has)."""
    n = int(cfg.get("num_layers", cfg["num_hidden_layers"]))
    published = cfg.get("published", {})
    first, count = cfg.get("experts_held", (0, int(cfg["num_experts"])))
    lin = cfg["linear_attn_config"]
    kinds = tuple("kda" if l + 1 in lin["kda_layers"] else "mla"
                  for l in range(n))
    if any(k == "mla" and l + 1 not in lin["full_attn_layers"]
           for l, k in enumerate(kinds)):
        raise ValueError("linear_attn_config names no kind for some layer")
    latent = int(cfg["kv_lora_rank"]) + int(cfg["qk_rope_head_dim"])
    return {
        "kinds": kinds, "L": n,
        "dense": tuple(range(min(n, int(cfg["first_k_dense_replace"])))),
        "H": int(cfg["hidden_size"]), "V": int(cfg["vocab_size"]),
        "F": int(cfg["intermediate_size"]),
        "kda_heads": int(lin["num_heads"]), "kda_hd": int(lin["head_dim"]),
        "conv": int(lin["short_conv_kernel_size"]),
        "decay_rank": int(cfg.get("decay_rank", lin["head_dim"])),
        "gate_rank": int(cfg.get("gate_rank", lin["head_dim"])),
        "heads": int(cfg["num_attention_heads"]),
        "nope": int(cfg["qk_nope_head_dim"]),
        "rope": int(cfg["qk_rope_head_dim"]),
        "vd": int(cfg["v_head_dim"]), "lora": int(cfg["kv_lora_rank"]),
        "latent": latent, "latent_width": -(-latent // 128) * 128,
        "E": int(published.get("num_experts", cfg["num_experts"])),
        "first": int(first), "count": int(count),
        "top_k": int(cfg["num_experts_per_token"]),
        "de": int(cfg["moe_intermediate_size"]),
        "ds": int(cfg["moe_intermediate_size"])
        * int(cfg["num_shared_experts"]),
        "routed_scale": float(cfg["routed_scaling_factor"]),
        "eps": float(cfg["rms_norm_eps"]),
    }


def layer_leaves(d: dict, l: int) -> list:
    """``(leaf, shape, distribution)`` of layer ``l``."""
    h = d["H"]
    leaves = [("input_norm.weight", (h,), "one")]
    if d["kinds"][l] == "kda":
        inner = d["kda_heads"] * d["kda_hd"]
        leaves += [
            ("mixer.qkv_proj.weight", (h, 3 * inner), "normal"),
            ("mixer.conv_weight", (d["conv"], 3 * inner), "normal"),
            ("mixer.f_a.weight", (h, d["decay_rank"]), "normal"),
            ("mixer.f_b.weight", (d["decay_rank"], inner), "normal"),
            ("mixer.dt_bias", (inner,), "dt_bias"),
            ("mixer.A_log", (d["kda_heads"],), "a_log"),
            ("mixer.b_proj.weight", (h, d["kda_heads"]), "normal"),
            ("mixer.g_a.weight", (h, d["gate_rank"]), "normal"),
            ("mixer.g_b.weight", (d["gate_rank"], inner), "normal"),
            ("mixer.g_bias", (inner,), "zero32"),
            ("mixer.o_norm_weight", (d["kda_hd"],), "one"),
            ("mixer.o_proj.weight", (inner, h), "normal"),
        ]
    else:
        n = d["heads"]
        leaves += [
            ("mixer.q_proj.weight", (h, n * (d["nope"] + d["rope"])),
             "normal"),
            ("mixer.kv_a.weight", (h, d["latent"]), "normal"),
            ("mixer.kv_norm_weight", (d["lora"],), "one"),
            ("mixer.kv_b.weight", (d["lora"], n * (d["nope"] + d["vd"])),
             "normal"),
            ("mixer.o_proj.weight", (n * d["vd"], h), "normal"),
        ]
    leaves.append(("post_norm.weight", (h,), "one"))
    if l in d["dense"]:
        return leaves + [
            ("mlp.w_in.weight", (h, 2 * d["F"]), "normal"),
            ("mlp.w_out.weight", (d["F"], h), "normal")]
    return leaves + [
        ("moe.router", (h, d["E"]), "normal"),
        ("moe.e_bias", (d["E"],), "e_bias"),
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

    def draw(key, shape, dist):
        f32 = jnp.float32
        if dist == "normal":
            return (jax.random.normal(key, shape, f32) * STD).astype(dtype)
        if dist == "one":
            return jnp.ones(shape, dtype)
        if dist == "zero32":
            return jnp.zeros(shape, f32)
        if dist == "e_bias":
            return jax.random.normal(key, shape, f32) * 0.01
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
