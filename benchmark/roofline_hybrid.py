"""The bytes a tick of the hybrid configuration must move, from its shapes
(``d`` = ``weights_hybrid.dims_of(config)``). Kept with the benchmark, like
``roofline.py``: the floor a share is read against cannot move with the
program.

A decode tick must read, once: the mixers', shared experts', routers' and
norms' weights of every layer and the held rows of the (tied) vocabulary;
the weights of every held expert THAT RECEIVED A ROW; the live pages of the
attention layers' K/V; and it must read and write the conv + SSM state of
every live row. Nothing else: activations of a few rows are noise beside
these.
"""

from __future__ import annotations


def mixer_params(d: dict, kind: str) -> int:
    h = d["H"]
    if kind == "attention":
        return h * (h + 2 * d["kv_heads"] * d["hd"]) + h * h
    return (h * (d["di"] + d["cd"] + d["nh"])      # in_proj
            + d["K"] * d["cd"] + d["cd"]           # conv weight + bias
            + 3 * d["nh"]                          # A_log, D, dt_bias
            + d["di"]                              # gated norm
            + d["di"] * h)                         # out_proj


def shared_params(d: dict) -> int:
    return 3 * d["H"] * d["ds"]


def router_params(d: dict) -> int:
    return d["H"] * d["E"]


def expert_params(d: dict) -> int:
    """One routed expert: ``W_in`` [H, 2 de] and ``W_out`` [de, H]."""
    return 3 * d["H"] * d["de"]


def fixed_params(d: dict) -> int:
    """What every tick reads whatever the routing: everything but the routed
    experts."""
    per_layer = shared_params(d) + router_params(d) + 2 * d["H"]
    return (sum(mixer_params(d, k) for k in d["kinds"])
            + d["L"] * per_layer + d["V"] * d["H"] + d["H"])


def total_params(d: dict) -> int:
    return fixed_params(d) + d["L"] * d["count"] * expert_params(d)


def weight_bytes(d: dict, bytes_per_param: float = 2) -> float:
    return total_params(d) * bytes_per_param


def state_layers(d: dict) -> int:
    return sum(k == "mamba" for k in d["kinds"])


def ssm_state_bytes_per_row(d: dict, bytes_per_value: float = 4) -> float:
    return state_layers(d) * d["nh"] * d["dh"] * d["N"] * bytes_per_value


def conv_state_bytes_per_row(d: dict, bytes_per_value: float = 2) -> float:
    return state_layers(d) * (d["K"] - 1) * d["cd"] * bytes_per_value


def state_bytes_per_row(d: dict, ssm_value_bytes: float = 4,
                        conv_value_bytes: float = 2) -> float:
    return ssm_state_bytes_per_row(d, ssm_value_bytes) \
        + conv_state_bytes_per_row(d, conv_value_bytes)


def page_bytes(d: dict, page_size: int, kv_value_bytes: float = 2) -> float:
    """K and V of one page over the attention layers."""
    attn = sum(k == "attention" for k in d["kinds"])
    return 2.0 * attn * page_size * d["kv_heads"] * d["hd"] * kv_value_bytes


def decode_tick_bytes(d: dict, experts_touched: float, state_rows: float,
                      kv_pages_live: float, page_size: int,
                      w_bytes: float = 2, kv_value_bytes: float = 2,
                      ssm_value_bytes: float = 4,
                      conv_value_bytes: float = 2) -> float:
    """``experts_touched``: held experts that received a row, summed over
    layers; ``state_rows``: live rows; ``kv_pages_live``: their pages."""
    return (fixed_params(d) * w_bytes
            + experts_touched * expert_params(d) * w_bytes
            + 2.0 * state_rows * state_bytes_per_row(
                d, ssm_value_bytes, conv_value_bytes)
            + kv_pages_live * page_bytes(d, page_size, kv_value_bytes))
