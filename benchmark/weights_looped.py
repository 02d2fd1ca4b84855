"""Seeded weights for the looped (Ouro) configuration, made by the benchmark
on the device in ONE jitted call and handed to the program and to the plain
reference alike, under the program's leaf names
(``paddle_tpu/models/ouro.py``).

Distribution (``assumed`` in the configuration file): every matrix normal,
std 0.02, the embedding, the untied head and the gate's weight among them;
norms at one; the gate's bias zero. The sandwich norm gives every branch's
output unit scale whatever the matrices' scale, so the stack moves the
logits at this initialisation as it is.
"""

from __future__ import annotations

import math

from .weights import STD, key_words


def dims_of(cfg: dict) -> dict:
    """The sizes the generator, the reference and the roofline need, from a
    configuration file (the published keys at its top level)."""
    return {
        "L": int(cfg["num_hidden_layers"]), "H": int(cfg["hidden_size"]),
        "V": int(cfg["vocab_size"]), "F": int(cfg["intermediate_size"]),
        "heads": int(cfg["num_attention_heads"]),
        "kv_heads": int(cfg["num_key_value_heads"]),
        "hd": int(cfg["head_dim"]), "steps": int(cfg["total_ut_steps"]),
        "eps": float(cfg["rms_norm_eps"]),
        "theta": float(cfg["rope_theta"]),
        "threshold": float(cfg["early_exit_threshold"]),
    }


def layer_leaves(d: dict) -> list:
    """``(leaf, shape, distribution)`` of one layer."""
    h, q, kv = d["H"], d["heads"] * d["hd"], d["kv_heads"] * d["hd"]
    return [
        ("input_norm.weight", (h,), "one"),
        ("attn.qkv_proj.weight", (h, q + 2 * kv), "normal"),
        ("attn.o_proj.weight", (q, h), "normal"),
        ("post_attn_norm.weight", (h,), "one"),
        ("pre_mlp_norm.weight", (h,), "one"),
        ("mlp.gate_up.weight", (h, 2 * d["F"]), "normal"),
        ("mlp.down.weight", (d["F"], h), "normal"),
        ("post_mlp_norm.weight", (h,), "one"),
    ]


def top_leaves(d: dict) -> list:
    h, v = d["H"], d["V"]
    return [("embed.weight", (v, h), "normal"),
            ("final_norm.weight", (h,), "one"),
            ("gate.weight", (h, 1), "normal"),
            ("gate.bias", (1,), "zero"),
            ("lm_head.weight", (h, v), "normal")]


def n_params(d: dict) -> int:
    return d["L"] * sum(math.prod(s) for _, s, _ in layer_leaves(d)) \
        + sum(math.prod(s) for _, s, _ in top_leaves(d))


def make(d: dict, seed: int, dtype) -> dict:
    """``{program leaf name: array}`` on the default device, one jitted
    call (a leaf's random bits are made and cast one leaf at a time)."""
    import jax
    import jax.numpy as jnp

    names = top_leaves(d) + [
        (f"layers.{l}.{name}", shape, dist) for l in range(d["L"])
        for name, shape, dist in layer_leaves(d)]

    def build(words):
        base = jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32),
                                        impl="rbg")
        out = {}
        for i, (name, shape, dist) in enumerate(names):
            if dist == "normal":
                out[name] = (jax.random.normal(
                    jax.random.fold_in(base, i), shape, jnp.float32)
                    * STD).astype(dtype)
            else:
                out[name] = jnp.full(shape, 1.0 if dist == "one" else 0.0,
                                     dtype)
        return out

    return jax.jit(build)(key_words(seed))
