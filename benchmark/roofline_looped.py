"""The bytes a decode tick of the looped configuration must move, from its
shapes (``d`` = ``weights_looped.dims_of(config)``). Kept with the
benchmark, like ``roofline.py``: the floor a share is read against cannot
move with the program.

A decode tick must read the ``L`` layers' weights ONCE A PASS (pass ``t + 1``
reads pass ``t``'s output of the last layer, and the stack is forty times
the chip's fast memory: nothing of it can stay between passes); the final
norm, the gate and the untied head once; and every live page of the rows it
serves over ALL ``L x steps`` cache layers. Nothing else: a few rows'
activations, their embedding rows and the K/V they write are noise beside
these.
"""

from __future__ import annotations


def layer_params(d: dict) -> int:
    h, q, kv = d["H"], d["heads"] * d["hd"], d["kv_heads"] * d["hd"]
    return h * (q + 2 * kv) + q * h + 3 * h * d["F"] + 4 * h


def top_params(d: dict) -> int:
    """Final norm, gate (weight and bias) and head: what a tick reads beside
    the stack (the embedding is read a row a token)."""
    return d["H"] + d["H"] + 1 + d["H"] * d["V"]


def total_params(d: dict) -> int:
    return d["L"] * layer_params(d) + top_params(d) + d["V"] * d["H"]


def cache_layers(d: dict) -> int:
    return d["L"] * d["steps"]


def page_bytes(d: dict, page_size: int, kv_value_bytes: float = 2) -> float:
    """K and V of one page over every cache layer."""
    return 2.0 * cache_layers(d) * page_size * d["kv_heads"] * d["hd"] \
        * kv_value_bytes


def decode_tick_bytes(d: dict, kv_pages_live: float, page_size: int,
                      w_bytes: float = 2, kv_value_bytes: float = 2) -> float:
    return (d["steps"] * d["L"] * layer_params(d) * w_bytes
            + top_params(d) * w_bytes
            + kv_pages_live * page_bytes(d, page_size, kv_value_bytes))
