"""The table of peaks, and the operations and bytes an algorithm needs, from
its shapes. Kept with the benchmark so that no PR that claims a gain can move
the yardstick.

Peaks: Google Cloud documentation, "TPU v5e" (system architecture page): one
chip 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2e at 819 GB/s. JAX reports
the chip as ``TPU v5 lite``. A device kind that is not in the table is an
error, never a default.
"""

from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "int8_ops": 393e12,
                    "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9,
                    "source": "Google Cloud docs, TPU v5e"},
    "TPU v5e": {"bf16_flops": 197e12, "int8_ops": 393e12,
                "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9,
                "source": "Google Cloud docs, TPU v5e"},
}


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"device kind {device_kind!r} is not in the peaks "
                       f"table {sorted(PEAKS)}: add it with its source") \
            from None


def matmul_params(d: dict) -> int:
    """Parameters that take part in a matrix product for every token: the
    blocks' four linear layers and the (tied) output head."""
    per_layer = 3 * d["H"] * d["H"] + d["H"] * d["H"] + 2 * d["H"] * d["F"]
    return d["L"] * per_layer + d["V"] * d["H"]


def attention_flops_per_token(d: dict, context: float) -> float:
    """Forward QK^T and PV for one query over ``context`` keys, all heads and
    layers: 2 products x 2 flops x context x H."""
    return 4.0 * d["L"] * d["H"] * context


def forward_flops_per_token(d: dict, context: float) -> float:
    return 2.0 * matmul_params(d) + attention_flops_per_token(d, context)


def train_flops_per_token(d: dict, seq: int) -> float:
    """Forward + backward (2x forward) for causal sequences of ``seq``: the
    mean query sees seq / 2 keys. Recomputation is not counted."""
    return 3.0 * forward_flops_per_token(d, seq / 2.0)


def weight_bytes(d: dict, bytes_per_param: float) -> float:
    per_layer = (4 * d["H"] * d["H"] + 2 * d["H"] * d["F"]
                 + 9 * d["H"] + d["F"])
    return (d["L"] * per_layer + (d["V"] + d["P"] + 2) * d["H"]) \
        * bytes_per_param


def kv_bytes_per_token(d: dict, bytes_per_value: float) -> float:
    """K and V of one token over all layers (MHA: kv heads x head size = H)."""
    return 2.0 * d["L"] * d["H"] * bytes_per_value


def decode_tick_bytes(d: dict, live_tokens: float, w_bytes: float,
                      kv_value_bytes: float) -> float:
    """The least one decode tick must read: every weight once and the live
    keys and values once."""
    return weight_bytes(d, w_bytes) + live_tokens * kv_bytes_per_token(
        d, kv_value_bytes)


def flash_attention_flops(batch: int, seq: int, heads: int, head_dim: int,
                          causal: bool = True, backward: bool = False) -> float:
    """QK^T and PV: 4 x b x h x s^2 x d forward (half under a causal mask);
    the backward pass is 2.5x the forward (dq, dk, dv and the recomputed
    scores)."""
    f = 4.0 * batch * heads * seq * seq * head_dim * (0.5 if causal else 1.0)
    return f * 2.5 if backward else f


def paged_attention_bytes(context_lens, heads: int, head_dim: int,
                          kv_value_bytes: float) -> float:
    """Keys and values a ragged decode batch must read: sum of contexts."""
    return 2.0 * float(sum(context_lens)) * heads * head_dim * kv_value_bytes


def roofline_seconds(flops: float, bytes_: float, peaks: dict) -> dict:
    c, m = flops / peaks["bf16_flops"], bytes_ / peaks["hbm_bytes_per_s"]
    return {"seconds": max(c, m), "bound": "compute" if c >= m else "memory"}
