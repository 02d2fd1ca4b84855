"""The bytes and operations a tick of the delta-rule / latent-attention
configuration must move, from its shapes (``d`` =
``weights_kda.dims_of(config)``). Kept with the benchmark, like
``roofline.py``: the floor a share is read against cannot move with the
program.

A decode tick must read, once: the mixers' matrices (KDA: the fused q / k / v
projection, the convolution, the decay's and the gate's low-rank pairs, the
write strength, the output projection; MLA: the query projection, the latent
projection, its expansion and the output projection), the shared-expert,
router and norm weights of every layer, the dense layer's feed-forward and
the untied head (the embedding is read a row a token: not counted); the
weights of every held expert THAT RECEIVED A ROW; of every LIVE row its
convolution tail and its delta-rule state in every KDA layer, read AND
written (a recurrence leaves a new state behind: twice the rows' bytes); and
every live page of the latent cache group at the bytes a row is STORED in
(``latent_width``, the padding to whole lanes included: it crosses HBM too).
Nothing else: activations of a few rows are noise beside these.
"""

from __future__ import annotations


def kda_params(d: dict) -> int:
    h, inner = d["H"], d["kda_heads"] * d["kda_hd"]
    return (h * 3 * inner + d["conv"] * 3 * inner
            + h * d["decay_rank"] + d["decay_rank"] * inner + inner
            + d["kda_heads"] + h * d["kda_heads"]
            + h * d["gate_rank"] + d["gate_rank"] * inner + inner
            + d["kda_hd"] + inner * h)


def mla_params(d: dict) -> int:
    h, n = d["H"], d["heads"]
    return (h * n * (d["nope"] + d["rope"]) + h * d["latent"] + d["lora"]
            + d["lora"] * n * (d["nope"] + d["vd"]) + n * d["vd"] * h)


def mixer_params(d: dict, l: int) -> int:
    return kda_params(d) if d["kinds"][l] == "kda" else mla_params(d)


def dense_params(d: dict) -> int:
    return 3 * d["H"] * d["F"]


def shared_params(d: dict) -> int:
    return 3 * d["H"] * d["ds"]


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
    return (sum(mixer_params(d, l) + 2 * d["H"] for l in range(d["L"]))
            + len(d["dense"]) * dense_params(d)
            + routed_layers(d) * (shared_params(d) + router_params(d))
            + d["H"] * d["V"] + d["H"])


def total_params(d: dict) -> int:
    return (fixed_params(d) + d["V"] * d["H"]
            + routed_layers(d) * d["count"] * expert_params(d))


def weight_bytes(d: dict, bytes_per_param: float = 2) -> float:
    return total_params(d) * bytes_per_param


def kda_layers(d: dict) -> int:
    return sum(k == "kda" for k in d["kinds"])


def mla_layers(d: dict) -> int:
    return d["L"] - kda_layers(d)


def state_row_bytes(d: dict, conv_value_bytes: float = 2) -> float:
    """What ONE sequence's recurrent state is, over the KDA layers: the
    convolution's tail (activations' type) and the delta rule's ``[heads, d,
    d]`` state (float32)."""
    inner = d["kda_heads"] * d["kda_hd"]
    return kda_layers(d) * ((d["conv"] - 1) * 3 * inner * conv_value_bytes
                            + d["kda_heads"] * d["kda_hd"] ** 2 * 4.0)


def page_bytes(d: dict, page_size: int, kv_value_bytes: float = 2) -> float:
    """One page of the latent group over the MLA layers, as stored."""
    return mla_layers(d) * page_size * d["latent_width"] * kv_value_bytes


def decode_tick_bytes(d: dict, experts_touched: float, state_rows: float,
                      latent_pages: float, page_size: int,
                      w_bytes: float = 2, kv_value_bytes: float = 2) -> float:
    """``experts_touched``: held experts that received a row, summed over
    layers; ``state_rows``: the live rows whose state the tick advances;
    ``latent_pages``: the live pages of the latent group."""
    return (fixed_params(d) * w_bytes
            + experts_touched * expert_params(d) * w_bytes
            + 2.0 * state_rows * state_row_bytes(d, kv_value_bytes)
            + latent_pages * page_bytes(d, page_size, kv_value_bytes))


def token_flops(d: dict, context: int) -> float:
    """Multiply-adds x 2 of one token at ``context`` cached positions on
    this chip: its products with the weights held here (``top_k`` routed
    experts a token, of which ``count / E`` fall here on average), its
    delta-rule step (the decay, two reads and a rank-one write of ``[d,
    d]`` a head) and its absorbed attention over the context (a head
    against ``latent`` columns for the scores and ``lora`` for the sum)."""
    per_token = fixed_params(d) + routed_layers(d) * d["top_k"] \
        * d["count"] / d["E"] * expert_params(d)
    step = kda_layers(d) * 4 * d["kda_heads"] * d["kda_hd"] ** 2
    attn = mla_layers(d) * d["heads"] * (d["latent"] + d["lora"]) * context
    return 2.0 * (per_token + step + attn)
