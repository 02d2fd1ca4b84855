"""Seeded weights for the sink / unequal-widths configuration (MiMo-V2-Flash:
full and sliding attention layers with their own K/V heads, keys of 192
beside values of 128, a learned sink a query head on the sliding layers, a
leading dense layer, then sigmoid-routed experts and no shared one), made by
the benchmark on the device and handed to the program and to the plain
reference alike, under the program's leaf names
(``paddle_tpu/models/mimo_v2.py``).

One jitted call a layer (a program a shape of layer) and one for the top: the
random bits of ten layers at once would not fit beside the 9.9 GB they make.
The same seed gives the same arrays.

Distribution (``assumed`` in the configuration file): every matrix normal,
std 0.02 (the embedding and the untied head among them); norms at one;
``e_bias`` normal std 0.01; the sinks normal std 1 about ``ln(window) + 1``
(``sink_mean``: of the size of the log of a window's summed scores, so that a
sink takes about half of its head's mass and the term matters at seeded
weights; about 0 it is 0.5% of the denominator at the published widths and
the reference without it reads as sound, my chip run PR 42). ``e_bias`` and
the sinks are float32 whatever the weights' type.
"""

from __future__ import annotations

import math

from .weights import STD, key_words

FULL, SLIDING = "full", "sliding"


def dims_of(cfg: dict) -> dict:
    """The sizes the generators, the reference and the roofline need, from a
    configuration file (published keys at its top level; ``num_layers``
    (``num_hidden_layers`` where the file has no cut of depth),
    ``n_routed_experts`` and ``vocab_size`` are what is held here,
    ``published`` what the source has). A kind's sizes stand under its
    prefix: ``full_*`` and ``swa_*``."""
    n = int(cfg.get("num_layers", cfg["num_hidden_layers"]))
    published = cfg.get("published", {})
    first, count = cfg.get("experts_held",
                           (0, int(cfg["n_routed_experts"])))
    if cfg.get("n_shared_experts") or cfg.get("routed_scaling_factor") \
            or cfg.get("scoring_func", "sigmoid") != "sigmoid":
        raise ValueError("the published router: sigmoid scores, no shared "
                         "expert, routed_scaling_factor null")
    hd, swa_hd = int(cfg["head_dim"]), int(cfg["swa_head_dim"])

    def rot(width):
        r = int(float(cfg["partial_rotary_factor"]) * width)
        return r - r % 2

    return {
        "kinds": tuple(SLIDING if int(v) else FULL
                       for v in cfg["hybrid_layer_pattern"][:n]),
        "dense": tuple(l for l in range(n)
                       if not int(cfg["moe_layer_freq"][l])),
        "L": n, "H": int(cfg["hidden_size"]), "V": int(cfg["vocab_size"]),
        "F": int(cfg["intermediate_size"]),
        "full_heads": int(cfg["num_attention_heads"]),
        "full_kv": int(cfg["num_key_value_heads"]),
        "full_hd": hd, "full_vd": int(cfg["v_head_dim"]),
        "full_theta": float(cfg["rope_theta"]), "full_rot": rot(hd),
        "full_sink": bool(cfg["add_full_attention_sink_bias"]),
        "swa_heads": int(cfg["swa_num_attention_heads"]),
        "swa_kv": int(cfg["swa_num_key_value_heads"]),
        "swa_hd": swa_hd, "swa_vd": int(cfg["swa_v_head_dim"]),
        "swa_theta": float(cfg["swa_rope_theta"]), "swa_rot": rot(swa_hd),
        "swa_sink": bool(cfg["add_swa_attention_sink_bias"]),
        "window": int(cfg["sliding_window"]),
        "sink_mean": math.log(int(cfg["sliding_window"])) + 1.0,
        "vscale": float(cfg["attention_value_scale"]),
        "E": int(published.get("n_routed_experts",
                               cfg["n_routed_experts"])),
        "first": int(first), "count": int(count),
        "top_k": int(cfg["num_experts_per_tok"]),
        "de": int(cfg["moe_intermediate_size"]),
        "routed_scale": 1.0,
        "eps": float(cfg["layernorm_epsilon"]),
    }


def prefix(kind: str) -> str:
    return "full_" if kind == FULL else "swa_"


def key_width(d: dict, kind: str) -> int:
    """A key as the program STORES it: whole lanes of 128."""
    return -(-d[prefix(kind) + "hd"] // 128) * 128


def layer_leaves(d: dict, l: int) -> list:
    """``(leaf, shape, distribution)`` of layer ``l``."""
    h, p = d["H"], prefix(d["kinds"][l])
    n, kv, hd, vd = (d[p + k] for k in ("heads", "kv", "hd", "vd"))
    leaves = [
        ("input_norm.weight", (h,), "one"),
        ("attn.qkv_proj.weight", (h, n * hd + kv * (hd + vd)), "normal"),
        ("attn.o_proj.weight", (n * vd, h), "normal")]
    if d[p + "sink"]:
        leaves.append(("attn.sinks", (n,), "sink"))
    leaves.append(("post_norm.weight", (h,), "one"))
    if l in d["dense"]:
        return leaves + [
            ("mlp.w_in.weight", (h, 2 * d["F"]), "normal"),
            ("mlp.w_out.weight", (d["F"], h), "normal")]
    return leaves + [
        ("moe.router", (h, d["E"]), "normal"),
        ("moe.e_bias", (d["E"],), "e_bias"),
        ("moe.w_in", (d["count"], h, 2 * d["de"]), "normal"),
        ("moe.w_out", (d["count"], d["de"], h), "normal")]


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

    sink_mean = d["sink_mean"]

    def draw(key, shape, dist):
        f32 = jnp.float32
        if dist == "normal":
            return (jax.random.normal(key, shape, f32) * STD).astype(dtype)
        if dist == "one":
            return jnp.ones(shape, dtype)
        if dist == "e_bias":
            return jax.random.normal(key, shape, f32) * 0.01
        if dist == "sink":
            return jax.random.normal(key, shape, f32) + sink_mean
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
