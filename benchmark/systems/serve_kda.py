"""The delta-rule / latent-attention serving system under test:
``KimiLinearForCausalLM`` (a delta-rule state a slot beside one latent cache
group, sigmoid-routed experts cut to this chip's share) in ``LLMEngine``
behind ``serve_llm``, driven over HTTP by the load generator child.

The run IS ``systems/serve_swa.py``'s: the same warm-up, window, trace,
drain and lines. That body names its configuration's three things as module
globals (``weights_swa``, ``build_net``, ``check_served``), so it is run
here over THIS module's (:func:`_with`: the same code object, other
globals) and not written out a fifth time; a ``benchmark`` issue folds the
five drivers into one that takes them as arguments (ROADMAP A0b(g));
``tests/benchmark/test_kda.py`` pins what :func:`_with` rests on. The check
samples as ``serve_swa.check_served`` does, against
``reference/kimi_linear.py``, and holds a second number beside the worst
gap (:func:`check_served`)."""

from __future__ import annotations

import time
import types

from .. import weights_kda
from . import serve_swa


def build_net(model: dict, params: dict):
    """The program's network around the benchmark's arrays (the constructor's
    own initialisers run under ``eval_shape``: nothing is computed)."""
    import jax
    from paddle_tpu.models.kimi_linear import (KimiLinearConfig,
                                               KimiLinearForCausalLM)
    published = model.get("published", {})
    keys = ("vocab_size", "hidden_size", "intermediate_size",
            "num_attention_heads", "kv_lora_rank", "q_lora_rank",
            "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
            "mla_use_nope", "linear_attn_config", "first_k_dense_replace",
            "moe_intermediate_size", "num_experts_per_token",
            "num_shared_experts", "routed_scaling_factor",
            "moe_renormalize", "moe_router_activation_func", "rms_norm_eps",
            "model_max_length")
    cfg = KimiLinearConfig(
        num_hidden_layers=int(model.get("num_layers",
                                        model["num_hidden_layers"])),
        num_experts=int(published.get("num_experts", model["num_experts"])),
        experts_held=model.get("experts_held"),
        **{k: model[k] for k in keys},
        **{k: model[k] for k in ("decay_rank", "gate_rank") if k in model})
    box = {}

    def construct():
        box["net"] = KimiLinearForCausalLM(cfg)
        return 0

    jax.eval_shape(construct)
    import paddle_tpu as pt
    pt.seed(0)
    net = box["net"]
    net.set_state_dict(params)
    return net


def held(gaps, spec: dict) -> dict:
    """The two held numbers of one set of gaps (a served token's logit below
    the reference's best, 0 where it IS the best), and whether both hold."""
    worst, share = float(gaps.max()), float((gaps == 0).mean())
    return {"worst_gap": worst, "argmax_share": share,
            "mean_gap": float(gaps.mean()),
            "correct": bool(worst <= spec["worst_gap_limit"]
                            and share >= spec["argmax_share_min"])}


def check_served(ctx, params, d, ok: list, kind, spec: dict) -> dict:
    """As ``serve.check_served``, against ``reference/kimi_linear.py``:
    teacher-force a seeded sample of the window's finished requests, the
    longest among them, and read how far each served token's logit lies
    below the reference's best. TWO numbers are held, and a run is correct
    by both: the worst such gap (``worst_gap_limit``) and the share of
    served tokens that ARE the reference's best (``argmax_share_min``). The
    worst gap alone saturates here (27 layers of seeded weights, logits of
    std ~0.96: PERF.md section 4); the share is a mean over ~3,000 tokens
    and moves with every layer's precision."""
    import jax
    import numpy as np
    from ..reference import kimi_linear
    from ..traffic import shapes
    if not ok:
        ctx.say({"check": "no finished request to compare"})
        return {"correct": False}
    t_ref = time.monotonic()
    order = sorted(ok, key=lambda r: (r["n_prompt"] + len(r["output_ids"]),
                                      r["index"]))
    longest, rest = order[-1], order[:-1]
    pick = shapes.rng(ctx.seed, 9).permutation(len(rest))[
        :max(int(spec["sample"]) - 1, 0)]
    chosen = [longest] + [rest[int(i)] for i in pick]
    again = kind.prompts(ctx.workload, ctx.seed, d["V"],
                         [r["index"] for r in chosen])
    pad = int(spec["pad_to"])
    ids = np.zeros((len(chosen), pad), np.int32)
    served = np.zeros((len(chosen), pad), np.int32)
    first = np.zeros(len(chosen), np.int32)
    count = np.zeros(len(chosen), np.int32)
    for b, r in enumerate(chosen):
        prompt, out = again[r["index"]], r["output_ids"]
        if len(prompt) != r["n_prompt"]:
            raise RuntimeError("a regenerated prompt has another length")
        seq = list(prompt) + list(out)
        ids[b, :len(seq)] = seq
        first[b] = len(prompt) - 1
        count[b] = len(out)
        served[b, len(prompt) - 1:len(seq) - 1] = out
    quant = spec["control"] if ctx.control else None
    got = jax.device_get(kimi_linear.served_gaps(
        params, ids, first, count, served, d, quant))
    mask = got["mask"]
    gaps = got["gap"][mask]
    miss = gaps > 0
    mine = held(gaps, spec)
    line = {"check": "served tokens against the float32 reference",
            "requests": len(chosen), "served_tokens": int(mask.sum()),
            "longest_tokens": longest["n_prompt"]
            + len(longest["output_ids"]),
            "argmax_share": mine["argmax_share"],
            "argmax_share_min": spec["argmax_share_min"],
            "mean_gap_where_not_argmax": float(gaps[miss].mean())
            if miss.any() else 0.0,
            "mean_gap": mine["mean_gap"],
            "worst_gap": mine["worst_gap"], "limit": spec["worst_gap_limit"],
            "distinct_served_tokens": int(len(np.unique(served[mask]))),
            "reference_seconds": round(time.monotonic() - t_ref, 2)}
    if quant:
        line["control"] = {"quant": quant,
                           **held(got["control_gap"][mask], spec)}
    ctx.say(line)
    return {"correct": mine["correct"]}


def _with(fn, **names):
    """``fn``'s code over its own module's globals with ``names`` in the
    place of that module's."""
    return types.FunctionType(fn.__code__, {**fn.__globals__, **names},
                              fn.__name__, fn.__defaults__, fn.__closure__)


def run(ctx) -> dict:
    # a program without the model (a parent commit) fails here, at once
    import paddle_tpu.models.kimi_linear  # noqa: F401
    return _with(serve_swa.run, weights_swa=weights_kda,
                 build_net=build_net, check_served=check_served)(ctx)
