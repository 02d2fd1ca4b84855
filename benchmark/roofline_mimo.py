"""The bytes a tick of the sink / unequal-widths configuration
must move, from its shapes (``d`` = ``weights_mimo.dims_of(config)``). Kept
with the benchmark, like ``roofline.py``: the floor a share is read against
cannot move with the program.

A decode tick must read, once: the attention weights of every layer (``W_q``,
``W_k``, ``W_v``, ``W_o`` at the layer's own K/V heads and widths, the sinks
of a sliding layer), the router and its selection bias and the norm weights
of every layer, the dense layer's feed-forward and the untied head (the
embedding is read a row a token: not counted); the weights of every held
expert THAT RECEIVED A ROW; every live page of the ``full`` cache group (a
full layer's row reads its whole context); and of the ``window`` cache group
the pages that intersect a live row's window, whatever the sequence's length;
a page of either group at the bytes the MODEL's shapes give it, K and V each
at its own published width (a key 192, a value 128). The program stores a key
256 wide (zeros to whole lanes); the 64 lanes of padding are its choice, not
work a tick must do, so they are NOT in the floor: :func:`stored_page_terms`
gives the page terms as stored, printed beside the floor's, and a program
that stops moving the padding reads a higher share. Nothing else: activations
of a few rows are noise beside these.
"""

from __future__ import annotations

from .weights_mimo import FULL, SLIDING, key_width, prefix


def attention_params(d: dict, kind: str) -> int:
    """``W_q``, ``W_k``, ``W_v`` and ``W_o`` of a layer of ``kind``, and its
    sinks where it has them."""
    p = prefix(kind)
    n, kv, hd, vd = (d[p + k] for k in ("heads", "kv", "hd", "vd"))
    return d["H"] * (n * hd + kv * (hd + vd)) + n * vd * d["H"] \
        + (n if d[p + "sink"] else 0)


def dense_params(d: dict) -> int:
    return 3 * d["H"] * d["F"]


def router_params(d: dict) -> int:
    """The router's matrix and its selection bias."""
    return d["H"] * d["E"] + d["E"]


def expert_params(d: dict) -> int:
    """One routed expert: ``W_in`` [H, 2 de] and ``W_out`` [de, H]."""
    return 3 * d["H"] * d["de"]


def routed_layers(d: dict) -> int:
    return d["L"] - len(d["dense"])


def fixed_params(d: dict) -> int:
    """What every tick reads whatever the routing: everything but the routed
    experts and the embedding."""
    return (sum(attention_params(d, k) + 2 * d["H"] for k in d["kinds"])
            + len(d["dense"]) * dense_params(d)
            + routed_layers(d) * router_params(d)
            + d["H"] * d["V"] + d["H"])


def total_params(d: dict) -> int:
    return (fixed_params(d) + d["V"] * d["H"]
            + routed_layers(d) * d["count"] * expert_params(d))


def weight_bytes(d: dict, bytes_per_param: float = 2) -> float:
    return total_params(d) * bytes_per_param


def group_layers(d: dict, group: str) -> int:
    kind = {"full": FULL, "window": SLIDING}[group]
    return sum(k == kind for k in d["kinds"])


def row_bytes(d: dict, group: str, kv_value_bytes: float = 2,
              stored: bool = False) -> dict:
    """``{"k", "v"}``: what one token holds in cache group ``group`` over its
    layers, the key at its published width or, ``stored``, at the width the
    program keeps it (whole lanes)."""
    kind = {"full": FULL, "window": SLIDING}[group]
    p, layers = prefix(kind), group_layers(d, group)
    kd = key_width(d, kind) if stored else d[p + "hd"]
    return {"k": layers * d[p + "kv"] * kd * kv_value_bytes,
            "v": layers * d[p + "kv"] * d[p + "vd"] * kv_value_bytes}


def page_bytes(d: dict, group: str, page_size: int,
               kv_value_bytes: float = 2, stored: bool = False) -> float:
    """K and V of one page over the layers of cache group ``group``."""
    return page_size * sum(
        row_bytes(d, group, kv_value_bytes, stored).values())


def ring_pages(d: dict, page_size: int, prefill_chunk: int) -> int:
    """The most pages of the window group one sequence can hold."""
    return -(-(d["window"] + prefill_chunk) // page_size) + 1


def decode_tick_terms(d: dict, experts_touched: float, full_pages: float,
                      window_pages: float, page_size: int,
                      w_bytes: float = 2, kv_value_bytes: float = 2) -> dict:
    """The floor's four terms in bytes. ``experts_touched``: held experts
    that received a row, summed over layers; ``full_pages`` /
    ``window_pages``: the live pages of each cache group, a page's bytes its
    own group's."""
    return {
        "fixed_weights": fixed_params(d) * w_bytes,
        "experts_touched": experts_touched * expert_params(d) * w_bytes,
        "full_pages": full_pages * page_bytes(d, "full", page_size,
                                              kv_value_bytes),
        "window_pages": window_pages * page_bytes(d, "window", page_size,
                                                  kv_value_bytes)}


def stored_page_terms(d: dict, full_pages: float, window_pages: float,
                      page_size: int, kv_value_bytes: float = 2) -> dict:
    """The two page terms at the bytes the program STORES (a key in whole
    lanes): what it moves today, beside what the floor says it must."""
    return {g + "_pages": n * page_bytes(d, g, page_size, kv_value_bytes,
                                         stored=True)
            for g, n in (("full", full_pages), ("window", window_pages))}


def decode_tick_bytes(d: dict, experts_touched: float, full_pages: float,
                      window_pages: float, page_size: int,
                      w_bytes: float = 2, kv_value_bytes: float = 2) -> float:
    return sum(decode_tick_terms(d, experts_touched, full_pages,
                                 window_pages, page_size, w_bytes,
                                 kv_value_bytes).values())
