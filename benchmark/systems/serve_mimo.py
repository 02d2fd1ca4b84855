"""The sink / unequal-widths serving system under test: ``MiMoV2ForCausalLM``
(two cache groups with their own K/V heads and page lifetimes, K pages wider
than V pages, a sink in the sliding layers' softmax over a window shorter
than a prompt chunk, sigmoid-routed experts cut to this chip's share) in
``LLMEngine`` behind ``serve_llm``, driven over HTTP by the load generator
child.

The run IS ``systems/serve_swa.py``'s, borrowed as ``systems/serve_kda.py``
borrows it (``serve_kda._with``: the same code object over this module's
weights, constructor and check; ``tests/benchmark/test_kda.py`` pins what
that rests on, ``tests/benchmark/test_mimo.py`` that it holds here). The
check samples as ``serve_swa.check_served`` does, against
``reference/mimo_v2.py``, holds the two numbers ``serve_kda.held`` holds, and
with ``--control 1`` reads EVERY control the check file names
(``controls``: the lower precision, the sink left out, the value scale left
out), each of which has to fail."""

from __future__ import annotations

import time

from .. import weights_mimo
from . import serve_swa
from .serve_kda import _with, held

PUBLISHED_KEYS = (
    "vocab_size", "hidden_size", "intermediate_size", "num_attention_heads",
    "num_key_value_heads", "head_dim", "v_head_dim",
    "swa_num_attention_heads", "swa_num_key_value_heads", "swa_head_dim",
    "swa_v_head_dim", "max_position_embeddings", "layernorm_epsilon",
    "rope_theta", "swa_rope_theta", "partial_rotary_factor",
    "sliding_window", "attention_value_scale", "hybrid_layer_pattern",
    "add_swa_attention_sink_bias", "add_full_attention_sink_bias",
    "moe_layer_freq", "moe_intermediate_size", "n_shared_experts",
    "num_experts_per_tok", "norm_topk_prob", "scoring_func", "n_group",
    "topk_group", "topk_method", "routed_scaling_factor")


def build_net(model: dict, params: dict):
    """The program's network around the benchmark's arrays (the constructor's
    own initialisers run under ``eval_shape``: nothing is computed)."""
    import jax
    from paddle_tpu.models.mimo_v2 import MiMoV2Config, MiMoV2ForCausalLM
    published = model.get("published", {})
    cfg = MiMoV2Config(
        num_hidden_layers=int(model.get("num_layers",
                                        model["num_hidden_layers"])),
        n_routed_experts=int(published.get("n_routed_experts",
                                           model["n_routed_experts"])),
        experts_held=model.get("experts_held"),
        **{k: model[k] for k in PUBLISHED_KEYS})
    box = {}

    def construct():
        box["net"] = MiMoV2ForCausalLM(cfg)
        return 0

    jax.eval_shape(construct)
    import paddle_tpu as pt
    pt.seed(0)
    net = box["net"]
    net.set_state_dict(params)
    return net


class _Watch(serve_swa._StallWatch):
    """The borrowed run's stall watch, which also remembers the page pool of
    the engine it watches, for :func:`release_pools`."""

    pool = None

    def __init__(self, eng, *args, **kwargs):
        super().__init__(eng, *args, **kwargs)
        _Watch.pool = eng._pool


def release_pools() -> int:
    """Delete the page arrays of the run's engine, by reference; the bytes
    freed. The engine is closed when the check runs, but the borrowed run
    still reaches it (its stall watch, and its loop variable the last cache
    group), and with it 3.8 GB of pools that are no one's any more: beside
    9.85 GB of weights they leave the reference's programs under 2 GB, which
    the plain pass fits by a hair and a control's pass does not (my chip
    runs, PR 42). Nothing else on the device is touched."""
    import jax
    pool, _Watch.pool = _Watch.pool, None
    freed = 0
    for g in pool.groups if pool is not None else ():
        for a in jax.tree_util.tree_leaves((g.k_pages, g.v_pages)):
            freed += a.nbytes
            a.delete()
    return freed


def check_served(ctx, params, d, ok: list, kind, spec: dict) -> dict:
    """As ``serve_kda.check_served``, against ``reference/mimo_v2.py``:
    teacher-force a seeded sample of the window's finished requests, the
    longest among them, and read how far each served token's logit lies
    below the reference's best. TWO numbers are held, and a run is correct
    by both (``serve_kda.held``): the worst such gap and the share of served
    tokens that ARE the reference's best. With ``--control 1`` every control
    of ``spec["controls"]`` is read the same way, each on its own line of
    the ``controls`` list."""
    import jax
    import numpy as np
    from ..reference import mimo_v2
    from ..traffic import shapes
    if not ok:
        ctx.say({"check": "no finished request to compare"})
        return {"correct": False}
    t_ref = time.monotonic()
    freed = release_pools()
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
    quants = tuple(spec["controls"]) if ctx.control else ()
    got = jax.device_get(mimo_v2.served_gaps(
        params, ids, first, count, served, d, quants))
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
            "pool_bytes_freed_before": freed,
            "reference_seconds": round(time.monotonic() - t_ref, 2)}
    if quants:
        line["controls"] = [
            {"quant": q, **held(got["control_gap"][q][mask], spec)}
            for q in quants]
    ctx.say(line)
    return {"correct": mine["correct"]}


def run(ctx) -> dict:
    # a program without the model (a parent commit) fails here, at once
    import paddle_tpu.models.mimo_v2  # noqa: F401
    return _with(serve_swa.run, weights_swa=weights_mimo,
                 build_net=build_net, check_served=check_served,
                 _StallWatch=_Watch)(ctx)
