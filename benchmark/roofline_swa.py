"""The bytes and operations a tick of the window / full attention
configuration must move, from its shapes (``d`` =
``weights_swa.dims_of(config)``). Kept with the benchmark, like
``roofline.py``: the floor a share is read against cannot move with the
program.

A decode tick must read, once: the attention, head-gate, shared-expert,
router and norm weights of every layer, the dense layer's feed-forward and
the untied head (the embedding is read a row a token: not counted); the
weights of every held expert THAT RECEIVED A ROW; every live page of the
``full`` cache group (a full layer's row reads its whole context); and of
the ``window`` cache group the pages that intersect a live row's window,
whatever the sequence's length. Nothing else: activations of a few rows are
noise beside these.
"""

from __future__ import annotations

FULL, SLIDING = "full_attention", "sliding_attention"


def attention_params(d: dict, l: int) -> int:
    """``W_q``, ``W_k``, ``W_v``, the head gate ``W_g`` and ``W_o``."""
    h, n, kv = d["H"], d["heads"][l], d["kv_heads"] * d["hd"]
    return h * (n * d["hd"] + 2 * kv) + h * n + n * d["hd"] * h


def dense_params(d: dict) -> int:
    return 3 * d["H"] * d["F"]


def shared_params(d: dict) -> int:
    return 3 * d["H"] * d["ds"]


def router_params(d: dict) -> int:
    return d["H"] * d["E"]


def expert_params(d: dict) -> int:
    """One routed expert: ``W_in`` [H, 2 de] and ``W_out`` [de, H]."""
    return 3 * d["H"] * d["de"]


def routed_layers(d: dict) -> int:
    return d["L"] - len(d["dense"])


def fixed_params(d: dict) -> int:
    """What every tick reads whatever the routing: everything but the routed
    experts and the embedding."""
    return (sum(attention_params(d, l) + 2 * d["H"] for l in range(d["L"]))
            + len(d["dense"]) * dense_params(d)
            + routed_layers(d) * (shared_params(d) + router_params(d))
            + d["H"] * d["V"] + d["H"])


def total_params(d: dict) -> int:
    return (fixed_params(d) + d["V"] * d["H"]
            + routed_layers(d) * d["count"] * expert_params(d))


def weight_bytes(d: dict, bytes_per_param: float = 2) -> float:
    return total_params(d) * bytes_per_param


def group_layers(d: dict, group: str) -> int:
    kind = {"full": FULL, "window": SLIDING}[group]
    return sum(k == kind for k in d["kinds"])


def page_bytes(d: dict, group: str, page_size: int,
               kv_value_bytes: float = 2) -> float:
    """K and V of one page over the layers of cache group ``group``."""
    return 2.0 * group_layers(d, group) * page_size * d["kv_heads"] \
        * d["hd"] * kv_value_bytes


def ring_pages(d: dict, page_size: int, prefill_chunk: int) -> int:
    """The most pages of the window group one sequence can hold."""
    return -(-(d["window"] + prefill_chunk) // page_size) + 1


def decode_tick_bytes(d: dict, experts_touched: float, full_pages: float,
                      window_pages: float, page_size: int,
                      w_bytes: float = 2, kv_value_bytes: float = 2) -> float:
    """``experts_touched``: held experts that received a row, summed over
    layers; ``full_pages`` / ``window_pages``: the live pages of each cache
    group, a page's bytes its own group's."""
    return (fixed_params(d) * w_bytes
            + experts_touched * expert_params(d) * w_bytes
            + full_pages * page_bytes(d, "full", page_size, kv_value_bytes)
            + window_pages * page_bytes(d, "window", page_size,
                                        kv_value_bytes))


def token_flops(d: dict, context: int) -> float:
    """Multiply-adds x 2 of one token at ``context`` cached positions on
    this chip: its products with the weights held here (10 routed experts a
    token, of which ``count / E`` fall here on average) and its attention
    over the context (a sliding layer: over ``min(context, window)``)."""
    per_token = fixed_params(d) + routed_layers(d) * d["top_k"] \
        * d["count"] / d["E"] * expert_params(d)
    attn = sum(2 * d["heads"][l] * d["hd"]
               * (context if d["kinds"][l] == FULL
                  else min(context, d["window"]))
               for l in range(d["L"]))
    return 2.0 * (per_token + attn)
