"""The gated-delta-rule / full-attention serving system under test:
``OlmoHybridForCausalLM`` (a delta-rule state with one decay a head a slot
beside one cache group of thirty K/V heads, Q/K norms over the whole
projection, the whole vocabulary) in ``LLMEngine`` behind ``serve_llm``,
driven over HTTP by the load generator child.

The run IS ``systems/serve_swa.py``'s, borrowed as ``systems/serve_kda.py``
borrows it (``serve_kda._with``: the same code object over this module's
weights, constructor and check; ``tests/benchmark/test_kda.py`` pins what
that rests on, ``tests/benchmark/test_olmo.py`` that it holds here). The
check samples as ``serve_swa.check_served`` does, against
``reference/olmo_hybrid.py``, holds the two numbers ``serve_kda.held`` holds,
and with ``--control 1`` reads EVERY control the check file names
(``controls``: the lower precision, the write strength's factor 2 left out,
the Q/K norms left out), each of which has to fail, and after them the ones
under ``reported`` (a state rounded to bfloat16 a token), which need not."""

from __future__ import annotations

import time

from .. import weights_olmo
from . import serve_swa
from .serve import _snapshot
from .serve_kda import _with, held

PUBLISHED_KEYS = (
    "vocab_size", "hidden_size", "intermediate_size", "num_hidden_layers",
    "num_attention_heads", "num_key_value_heads", "hidden_act",
    "max_position_embeddings", "attention_bias", "rms_norm_eps",
    "tie_word_embeddings", "layer_types", "linear_num_key_heads",
    "linear_num_value_heads", "linear_key_head_dim", "linear_value_head_dim",
    "linear_conv_kernel_dim", "linear_allow_neg_eigval", "rope_parameters")


def build_net(model: dict, params: dict):
    """The program's network around the benchmark's arrays (the constructor's
    own initialisers run under ``eval_shape``: nothing is computed)."""
    import jax
    from paddle_tpu.models.olmo_hybrid import (OlmoHybridConfig,
                                               OlmoHybridForCausalLM)
    cfg = OlmoHybridConfig(
        num_layers=int(model.get("num_layers", model["num_hidden_layers"])),
        **{k: model[k] for k in PUBLISHED_KEYS})
    box = {}

    def construct():
        box["net"] = OlmoHybridForCausalLM(cfg)
        return 0

    jax.eval_shape(construct)
    import paddle_tpu as pt
    pt.seed(0)
    net = box["net"]
    net.set_state_dict(params)
    return net


def _snapshot_dense(eng) -> dict:
    """The borrowed run's snapshot for a model without routed experts: the
    engine keeps no pair counts, and the run's ``moe_pairs_inside`` reads
    0."""
    return dict(_snapshot(eng), moe_pairs=0)


class _Watch(serve_swa._StallWatch):
    """The borrowed run's stall watch, which also remembers the engine it
    watches, for :func:`release_cache`."""

    eng = None

    def __init__(self, eng, *args, **kwargs):
        super().__init__(eng, *args, **kwargs)
        _Watch.eng = eng


def release_cache() -> int:
    """Delete the page arrays and the state rows of the run's engine, by
    reference; the bytes freed. The engine is closed when the check runs,
    but the borrowed run still reaches it (its stall watch), and with it 8.7
    GB of pages and state that are no one's any more: beside 4.87 GB of
    weights they would leave the reference's float32 blocks no room. Nothing
    else on the device is touched."""
    import jax
    eng, _Watch.eng = _Watch.eng, None
    if eng is None:
        return 0
    freed = 0
    held_arrays = [(g.k_pages, g.v_pages) for g in eng._pool.groups] \
        + [eng.conv_state, eng.ssm_state]
    for a in jax.tree_util.tree_leaves(held_arrays):
        freed += a.nbytes
        a.delete()
    return freed


def check_served(ctx, params, d, ok: list, kind, spec: dict) -> dict:
    """As ``serve_mimo.check_served``, against ``reference/olmo_hybrid.py``:
    teacher-force a seeded sample of the window's finished requests, the
    longest among them, and read how far each served token's logit lies
    below the reference's best. TWO numbers are held, and a run is correct
    by both (``serve_kda.held``): the worst such gap and the share of served
    tokens that ARE the reference's best. With ``--control 1`` every control
    of ``spec["controls"]`` and then of ``spec["reported"]`` is read the
    same way, each on its own line of the ``controls`` list (``required``:
    whether it has to fail)."""
    import jax
    import numpy as np
    from ..reference import olmo_hybrid
    from ..traffic import shapes
    if not ok:
        ctx.say({"check": "no finished request to compare"})
        return {"correct": False}
    t_ref = time.monotonic()
    freed = release_cache()
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
    required = tuple(spec["controls"]) if ctx.control else ()
    quants = required + (tuple(spec.get("reported", ()))
                         if ctx.control else ())
    got = jax.device_get(olmo_hybrid.served_gaps(
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
            "cache_bytes_freed_before": freed,
            "reference_seconds": round(time.monotonic() - t_ref, 2)}
    if quants:
        line["controls"] = [
            {"quant": q, "required": q in required,
             **held(got["control_gap"][q][mask], spec)}
            for q in quants]
    ctx.say(line)
    return {"correct": mine["correct"]}


def run(ctx) -> dict:
    # a program without the model (a parent commit) fails here, at once
    import paddle_tpu.models.olmo_hybrid  # noqa: F401
    return _with(serve_swa.run, weights_swa=weights_olmo,
                 build_net=build_net, check_served=check_served,
                 _StallWatch=_Watch, _snapshot_hybrid=_snapshot_dense)(ctx)
