"""The bytes and operations a tick of the gated-delta-rule / full-attention
configuration must move, from its shapes (``d`` =
``weights_olmo.dims_of(config)``). Kept with the benchmark, like
``roofline.py``: the floor a share is read against cannot move with the
program.

A decode tick must read, once: every layer's matrices (a ``linear_attention``
layer: the fused q / k / v projection, the convolution, the decay's and the
write strength's projections, the output gate, the head norm and the output
projection; a ``full_attention`` layer: the fused q / k / v projection, the
two norms and the output projection; the SwiGLU and two norms of either), the
final norm and the untied head (the embedding is read a row a token: not
counted); of every LIVE row its convolution tail and its delta-rule state in
every ``linear_attention`` layer, read AND written (a recurrence leaves a new
state behind: twice the rows' bytes), AT THE PUBLISHED ``K x V`` (96 x 192:
the lanes a stored state is padded to are the program's choice and no part of
the floor); and every live page of the ``full`` cache group at the PUBLISHED
``kv_heads x head_dim`` (thirty heads: the two a stored page carries beside
them likewise). Nothing else: activations of a few rows are noise beside
these.
"""

from __future__ import annotations

LINEAR, FULL = "linear_attention", "full_attention"


def linear_params(d: dict) -> int:
    h, nh = d["H"], d["lin_heads"]
    conv = nh * (2 * d["lin_k"] + d["lin_v"])
    inner = nh * d["lin_v"]
    return (h * conv + d["conv"] * conv + 2 * h * nh + 2 * nh
            + 2 * h * inner + d["lin_v"])


def full_params(d: dict) -> int:
    q, kv = d["heads"] * d["hd"], d["kv_heads"] * d["hd"]
    return d["H"] * (q + 2 * kv) + q + kv + q * d["H"]


def layer_params(d: dict, l: int) -> int:
    """A layer: its mixer, the SwiGLU and the two norms on the branches'
    outputs."""
    mixer = linear_params(d) if d["kinds"][l] == LINEAR else full_params(d)
    return mixer + 3 * d["H"] * d["F"] + 2 * d["H"]


def fixed_params(d: dict) -> int:
    """What every tick reads: everything but the embedding."""
    return (sum(layer_params(d, l) for l in range(d["L"]))
            + d["H"] + d["H"] * d["V"])


def total_params(d: dict) -> int:
    return fixed_params(d) + d["V"] * d["H"]


def weight_bytes(d: dict, bytes_per_param: float = 2) -> float:
    return total_params(d) * bytes_per_param


def linear_layers(d: dict) -> int:
    return sum(k == LINEAR for k in d["kinds"])


def full_layers(d: dict) -> int:
    return d["L"] - linear_layers(d)


def state_row_bytes(d: dict, conv_value_bytes: float = 2,
                    value_width: int = None) -> float:
    """What ONE sequence's recurrent state is, over the ``linear_attention``
    layers: the convolution's tail (activations' type) and the rule's
    ``[heads, K, V]`` state (float32) at the published widths, or with a
    head's value as wide as ``value_width`` (what a program stores)."""
    conv = d["lin_heads"] * (2 * d["lin_k"] + d["lin_v"])
    return linear_layers(d) * (
        (d["conv"] - 1) * conv * conv_value_bytes
        + d["lin_heads"] * d["lin_k"] * (value_width or d["lin_v"]) * 4.0)


def token_bytes(d: dict, kv_value_bytes: float = 2,
                heads_stored: int = None) -> float:
    """One token of the ``full`` group over its layers, K and V: at the
    published head count, or at ``heads_stored``."""
    return full_layers(d) * 2 * (heads_stored or d["kv_heads"]) * d["hd"] \
        * kv_value_bytes


def decode_tick_terms(d: dict, state_rows: float, full_pages: float,
                      page_size: int, w_bytes: float = 2,
                      kv_value_bytes: float = 2) -> dict:
    """The floor's three terms. ``state_rows``: the live rows whose state
    the tick advances; ``full_pages``: the live pages of the ``full``
    group."""
    return {"weights": fixed_params(d) * w_bytes,
            "state": 2.0 * state_rows * state_row_bytes(d, kv_value_bytes),
            "pages": full_pages * page_size
            * token_bytes(d, kv_value_bytes)}


def decode_tick_bytes(d: dict, state_rows: float, full_pages: float,
                      page_size: int, w_bytes: float = 2,
                      kv_value_bytes: float = 2) -> float:
    return sum(decode_tick_terms(d, state_rows, full_pages, page_size,
                                 w_bytes, kv_value_bytes).values())


def token_flops(d: dict, context: int) -> float:
    """Multiply-adds x 2 of one token at ``context`` cached positions: its
    products with the weights, its delta-rule step (the decay, two reads and
    a rank-one write of ``[K, V]`` a head) and its attention over the
    context."""
    step = linear_layers(d) * 4 * d["lin_heads"] * d["lin_k"] * d["lin_v"]
    attn = full_layers(d) * 2 * d["heads"] * d["hd"] * context
    return 2.0 * (fixed_params(d) + step + attn)
