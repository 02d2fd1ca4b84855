"""Weights from ``--seed``, made by the benchmark, on the device, in one call.

The benchmark, not the program, makes the weights: one jitted call builds every
leaf in the type it is served or trained in, and both the system under test and
the plain reference are handed these arrays. Per-layer leaves are generated
stacked ``[L, ...]`` (one key a leaf, split over layers); ``unstack`` gives the
program's per-layer names. The same seed gives the same arrays, stacked or not.

Distribution: GPT-2/3's initialisation (normal, std 0.02; the two residual
output projections std 0.02 / sqrt(2 L); norms at one, biases at zero).
"""

from __future__ import annotations

import math

import numpy as np

# leaf -> (shape in terms of H, F, V, P; kind)
_LAYER_LEAVES = (
    ("ln_1.weight", ("H",), "one"),
    ("ln_1.bias", ("H",), "zero"),
    ("attn.qkv_proj.weight", ("H", "3H"), "normal"),
    ("attn.qkv_proj.bias", ("3H",), "zero"),
    ("attn.out_proj.weight", ("H", "H"), "normal_out"),
    ("attn.out_proj.bias", ("H",), "zero"),
    ("ln_2.weight", ("H",), "one"),
    ("ln_2.bias", ("H",), "zero"),
    ("mlp.fc_in.weight", ("H", "F"), "normal"),
    ("mlp.fc_in.bias", ("F",), "zero"),
    ("mlp.fc_out.weight", ("F", "H"), "normal_out"),
    ("mlp.fc_out.bias", ("H",), "zero"),
)
_TOP_LEAVES = (
    ("gpt.embeddings.word_embeddings.weight", ("V", "H"), "normal"),
    ("gpt.embeddings.position_embeddings.weight", ("P", "H"), "normal"),
    ("gpt.ln_f.weight", ("H",), "one"),
    ("gpt.ln_f.bias", ("H",), "zero"),
)
STD = 0.02


def dims_of(model: dict) -> dict:
    """The sizes the generators and the reference need, from a configuration
    file (the model's sizes are its top-level keys)."""
    h = int(model["hidden_size"])
    if h != int(model["num_heads"]) * int(model["head_dim"]):
        raise ValueError("hidden_size != num_heads * head_dim")
    return {"L": int(model["num_layers"]), "H": h,
            "heads": int(model["num_heads"]),
            "F": int(model["ffn_hidden_size"]),
            "V": int(model["vocab_size"]),
            "P": int(model["max_position_embeddings"]),
            "eps": float(model.get("layer_norm_epsilon", 1e-5))}


def _shape(spec, d):
    table = {"H": d["H"], "3H": 3 * d["H"], "F": d["F"], "V": d["V"],
             "P": d["P"]}
    return tuple(table[s] for s in spec)


def key_words(seed: int) -> np.ndarray:
    """Four uint32 words for an ``rbg`` key, from any whole number."""
    return np.random.SeedSequence([int(seed), 0x57E1]).generate_state(
        4, np.uint32)


def n_params(d: dict) -> int:
    per_layer = sum(int(np.prod(_shape(s, d))) for _, s, _ in _LAYER_LEAVES)
    top = sum(int(np.prod(_shape(s, d))) for _, s, _ in _TOP_LEAVES)
    return d["L"] * per_layer + top


def make_stacked(d: dict, words, dtype):
    """Traced body: ``{leaf: array}`` with layer leaves stacked ``[L, ...]``
    under the key ``layers.<leaf>``."""
    import jax
    import jax.numpy as jnp
    base = jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32),
                                    impl="rbg")
    out = {}
    names = [("layers." + n, (d["L"],) + _shape(s, d), k)
             for n, s, k in _LAYER_LEAVES]
    names += [(n, _shape(s, d), k) for n, s, k in _TOP_LEAVES]
    for i, (name, shape, kind) in enumerate(names):
        if kind == "one":
            out[name] = jnp.ones(shape, dtype)
        elif kind == "zero":
            out[name] = jnp.zeros(shape, dtype)
        else:
            std = STD / math.sqrt(2 * d["L"]) if kind == "normal_out" else STD
            k = jax.random.fold_in(base, i)
            out[name] = (jax.random.normal(k, shape, jnp.float32)
                         * std).astype(dtype)
    return out


def unstack(stacked: dict, d: dict) -> dict:
    """The program's names: ``gpt.layers.<l>.<leaf>``."""
    out = {}
    for name, v in stacked.items():
        if name.startswith("layers."):
            leaf = name[len("layers."):]
            for l in range(d["L"]):
                out[f"gpt.layers.{l}.{leaf}"] = v[l]
        else:
            out[name] = v
    return out


def make(d: dict, seed: int, dtype, stacked: bool = False) -> dict:
    """One jitted call on the default device."""
    import jax
    words = key_words(seed)

    def build(words):
        s = make_stacked(d, words, dtype)
        return s if stacked else unstack(s, d)

    return jax.jit(build)(words)


def program_name(stacked_name: str, layer: int) -> str:
    if stacked_name.startswith("layers."):
        return f"gpt.layers.{layer}.{stacked_name[len('layers.'):]}"
    return stacked_name
