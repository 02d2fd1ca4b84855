"""Seeded weights for the hybrid (Mamba-2 + attention + routed experts)
configuration, made by the benchmark on the device and handed to the program
and to the plain reference alike, under the program's leaf names
(``paddle_tpu/models/granite_hybrid.py``).

One jitted call a layer (two programs: a state-space layer, an attention
layer) and one for the top: a layer's largest leaf is 226M values, and the
random bits of all ten layers at once would not fit beside the 9.5 GB they
make. The same seed gives the same arrays.

Distribution: every matrix normal, std 0.02; the (tied) embedding std 0.02 /
``embedding_multiplier``, so that the scaled embedding enters the residual
stream at the scale of every other matrix (at std 0.02 times 12 the tied head
would answer every position with its own input token by a margin no rounding
could move, and ``correct`` would compare nothing); norms at one; and,
``assumed`` in the configuration file, the state-space
reference implementation's initialisers, so that the decays lie where a
trained model's do: ``dt = exp(U(log 1e-3, log 1e-1))`` through the inverse
softplus into ``dt_bias``, ``A ~ U(1, 16)`` as ``A_log``, ``D = 1``, the
convolution ``U(+-1/sqrt(d_conv))``.
"""

from __future__ import annotations

import math

from .weights import STD, key_words


def dims_of(cfg: dict) -> dict:
    """The sizes the generators, the reference and the roofline need, from a
    configuration file (published keys at its top level; ``num_layers``,
    ``num_local_experts`` and ``vocab_size`` are what is held here)."""
    kinds = tuple(cfg["layer_types"][:int(cfg["num_layers"])])
    h, heads = int(cfg["hidden_size"]), int(cfg["num_attention_heads"])
    nh, dh = int(cfg["mamba_n_heads"]), int(cfg["mamba_d_head"])
    n = int(cfg["mamba_d_state"])
    published = cfg.get("published", {})
    first, count = cfg.get("experts_held",
                           (0, int(cfg["num_local_experts"])))
    return {
        "kinds": kinds, "L": len(kinds), "H": h, "V": int(cfg["vocab_size"]),
        "heads": heads, "kv_heads": int(cfg["num_key_value_heads"]),
        "hd": h // heads, "nh": nh, "dh": dh, "N": n,
        "K": int(cfg["mamba_d_conv"]), "di": nh * dh,
        "cd": nh * dh + 2 * int(cfg["mamba_n_groups"]) * n,
        "chunk": int(cfg["mamba_chunk_size"]),
        "E": int(published.get("num_local_experts",
                               cfg["num_local_experts"])),
        "first": int(first), "count": int(count),
        "top_k": int(cfg["num_experts_per_tok"]),
        "de": int(cfg["intermediate_size"]),
        "ds": int(cfg["shared_intermediate_size"]),
        "eps": float(cfg["rms_norm_eps"]),
        "attention_multiplier": float(cfg["attention_multiplier"]),
        "embedding_multiplier": float(cfg["embedding_multiplier"]),
        "residual_multiplier": float(cfg["residual_multiplier"]),
        "logits_scaling": float(cfg["logits_scaling"]),
    }


def layer_leaves(d: dict, kind: str) -> list:
    """``(leaf, shape, distribution)`` of one layer of ``kind``."""
    h, kv = d["H"], d["kv_heads"] * d["hd"]
    common = [
        ("input_norm.weight", (h,), "one"),
        ("post_norm.weight", (h,), "one"),
        ("moe.router", (h, d["E"]), "normal"),
        ("moe.w_in", (d["count"], h, 2 * d["de"]), "normal"),
        ("moe.w_out", (d["count"], d["de"], h), "normal"),
        ("shared.w_in.weight", (h, 2 * d["ds"]), "normal"),
        ("shared.w_out.weight", (d["ds"], h), "normal"),
    ]
    if kind == "attention":
        return common + [
            ("mixer.qkv_proj.weight", (h, h + 2 * kv), "normal"),
            ("mixer.o_proj.weight", (h, h), "normal")]
    return common + [
        ("mixer.in_proj.weight", (h, d["di"] + d["cd"] + d["nh"]), "normal"),
        ("mixer.conv_weight", (d["K"], d["cd"]), "conv"),
        ("mixer.conv_bias", (d["cd"],), "conv"),
        ("mixer.dt_bias", (d["nh"],), "dt_bias"),
        ("mixer.A_log", (d["nh"],), "a_log"),
        ("mixer.D", (d["nh"],), "one"),
        ("mixer.norm_weight", (d["di"],), "one"),
        ("mixer.out_proj.weight", (d["di"], h), "normal")]


TOP_LEAVES = (("embed.weight", ("V", "H"), "embed"),
              ("final_norm.weight", ("H",), "one"))


def n_params(d: dict) -> int:
    total = d["V"] * d["H"] + d["H"]
    for kind in d["kinds"]:
        total += sum(math.prod(s) for _, s, _ in layer_leaves(d, kind))
    return total


def _draw(key, shape, dist, d, dtype):
    import jax
    import jax.numpy as jnp
    f32 = jnp.float32
    if dist == "one":
        return jnp.ones(shape, dtype)
    if dist == "normal":
        return (jax.random.normal(key, shape, f32) * STD).astype(dtype)
    if dist == "embed":
        return (jax.random.normal(key, shape, f32)
                * (STD / d["embedding_multiplier"])).astype(dtype)
    if dist == "conv":
        b = d["K"] ** -0.5
        return jax.random.uniform(key, shape, f32, -b, b).astype(dtype)
    if dist == "dt_bias":
        dt = jnp.exp(jax.random.uniform(key, shape, f32, math.log(1e-3),
                                        math.log(1e-1)))
        return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)
    if dist == "a_log":
        return jnp.log(jax.random.uniform(key, shape, f32, 1.0, 16.0)) \
            .astype(dtype)
    raise ValueError(dist)


def make(d: dict, seed: int, dtype) -> dict:
    """``{program leaf name: array}`` on the default device."""
    import jax
    import jax.numpy as jnp
    from functools import partial

    @partial(jax.jit, static_argnums=(2,))
    def build(words, index, leaves):
        base = jax.random.fold_in(jax.random.wrap_key_data(
            jnp.asarray(words, jnp.uint32), impl="rbg"), index)
        return {name: _draw(jax.random.fold_in(base, i), shape, dist, d,
                            dtype)
                for i, (name, shape, dist) in enumerate(leaves)}

    words = key_words(seed)
    sizes = {"V": d["V"], "H": d["H"]}
    top = tuple((n, tuple(sizes[s] for s in shape), dist)
                for n, shape, dist in TOP_LEAVES)
    out = dict(build(words, 0, top))
    for l, kind in enumerate(d["kinds"]):
        made = build(words, l + 1, tuple(layer_leaves(d, kind)))
        out.update({f"layers.{l}.{n}": v for n, v in made.items()})
    return out
