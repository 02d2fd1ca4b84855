"""The window / full attention serving system under test:
``LagunaForCausalLM`` (two cache groups with their own page lifetimes,
per-layer head counts, a per-head output gate, routed experts cut to this
chip's share) in ``LLMEngine`` behind ``serve_llm``, driven over HTTP by the
load generator child. The run is ``systems/serve.py``'s, with this
configuration's network, weights (``weights_swa.py``) and reference
(``reference/laguna_swa.py``) in GPT's place: ``serve.run`` names those
itself, so its body is repeated here, as in ``serve_hybrid.py`` and
``serve_looped.py``, until a ``benchmark`` issue folds the four (ROADMAP
A0b(g))."""

from __future__ import annotations

import gc
import importlib
import json
import os
import subprocess
import sys
import threading
import time

from .. import serve_metrics, stats, weights_swa
from .serve import _counter, _sleep_until
from .serve_hybrid import _StallWatch, _snapshot_hybrid


def build_net(model: dict, params: dict):
    """The program's network around the benchmark's arrays (the constructor's
    own initialisers run under ``eval_shape``: nothing is computed)."""
    import jax
    from paddle_tpu.models.laguna import LagunaConfig, LagunaForCausalLM
    published = model.get("published", {})
    keys = ("vocab_size", "hidden_size", "intermediate_size",
            "num_key_value_heads", "head_dim",
            "max_position_embeddings", "rms_norm_eps",
            "num_experts_per_tok", "moe_intermediate_size",
            "shared_expert_intermediate_size", "moe_routed_scaling_factor",
            "mlp_only_layers", "sliding_window", "layer_types",
            "num_attention_heads_per_layer", "rope_parameters")
    cfg = LagunaConfig(
        num_hidden_layers=int(model.get("num_layers",
                                        model["num_hidden_layers"])),
        num_experts=int(published.get("num_experts", model["num_experts"])),
        experts_held=model.get("experts_held"),
        **{k: model[k] for k in keys})
    box = {}

    def construct():
        box["net"] = LagunaForCausalLM(cfg)
        return 0

    jax.eval_shape(construct)
    import paddle_tpu as pt
    pt.seed(0)
    net = box["net"]
    net.set_state_dict(params)
    return net


def run(ctx) -> dict:
    import jax
    import jax.numpy as jnp
    # a program without the model (a parent commit) fails here, at once
    import paddle_tpu.models.laguna  # noqa: F401
    from paddle_tpu.inference.llm import LLMEngine, serve_llm

    cfg, mix = ctx.config, ctx.workload
    d = weights_swa.dims_of(cfg)
    dtype = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[
        cfg["precision"]["weights"]]
    params = weights_swa.make(d, ctx.seed, dtype)
    jax.block_until_ready(params)
    net = build_net(cfg, params)
    net.eval()
    ctx.mark("weights")

    st = jax.local_devices()[0].memory_stats() or {}
    eng = LLMEngine(net, **cfg["engine"])
    groups = [g.status() for g in eng._pool.groups]
    for g in eng._pool.groups:
        if g.num_pages - 1 < eng.max_seqs * (g.ring or eng.pages_per_seq):
            raise RuntimeError(f"cache group {g.name!r} cannot hold every "
                               f"slot's longest sequence: a request could "
                               f"be truncated")
    st2 = jax.local_devices()[0].memory_stats() or {}
    ctx.say({"sizing": {
        "parameters": weights_swa.n_params(d),
        "weights_bytes_in_use": st.get("bytes_in_use"),
        "with_pools_bytes_in_use": st2.get("bytes_in_use"),
        "bytes_limit": st.get("bytes_limit"), "cache_groups": groups}})
    srv = serve_llm(eng)
    url = "http://%s:%d" % srv.server_address[:2]
    env = {k: v for k, v in os.environ.items() if k != "BENCH_RUN"}
    child = subprocess.Popen(
        [sys.executable, os.path.join(ctx.bench_dir, "loadgen.py")],
        stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True, env=env)
    out_lines = []
    reader = threading.Thread(
        target=lambda: out_lines.extend(child.stdout), daemon=True)
    reader.start()
    try:
        child.stdin.write(json.dumps(
            {"url": url, "kind": mix["kind"], "traffic": mix,
             "seed": ctx.seed, "vocab": d["V"]}) + "\n")
        child.stdin.flush()
        ctx.mark("engine")

        # warm-up: the stream runs until every program it uses has compiled
        warm = mix["warmup"]
        t_begin = time.monotonic()
        while True:
            time.sleep(0.1)
            if child.poll() is not None:
                raise RuntimeError("the load generator exited in warm-up")
            done = _counter("llm_requests_completed")
            if done >= warm["min_requests"] \
                    and ctx.compile_quiet_for() >= warm["quiet_s"]:
                break
            if time.monotonic() - t_begin > warm["max_s"]:
                raise RuntimeError(
                    f"warm-up did not settle in {warm['max_s']} s "
                    f"({done} requests completed)")
        ctx.mark("warmup")
        compiles0 = ctx.compile_count()
        t0 = time.monotonic() + 0.2
        t_end = t0 + ctx.seconds
        child.stdin.write(f"go {t0!r} {ctx.seconds!r}\n")
        child.stdin.flush()
        _sleep_until(t0)
        ctx.window_opens()
        watch = _StallWatch(eng)
        watch.start()
        before = _snapshot_hybrid(eng)
        if ctx.trace:
            span = min(float(mix.get("trace_s", 3.0)), ctx.seconds * 0.5)
            ctx.trace_between(t0 + (ctx.seconds - span) / 2,
                              t0 + (ctx.seconds + span) / 2)
        _sleep_until(t_end)
        after = _snapshot_hybrid(eng)
        released = {g.name: g.n_released for g in eng._pool.groups}
        stalls = [dict(st, at=round(st["at"] - t0, 2))
                  for st in watch.stop()]
        steps = sorted(list(eng.step_durations)[
            -max(1, after["n_host_dispatches"]
                 - before["n_host_dispatches"]):])
        compiled_inside = ctx.compile_count() - compiles0
        child.wait(timeout=float(mix.get("drain_s", 120)) + 60)
        drained_s = time.monotonic() - t_end
        if ctx.trace:
            ctx.trace_join()
        reader.join(10)
        result = json.loads(out_lines[-1])
    finally:
        if child.poll() is None:
            child.kill()
            child.wait()
        health = eng.health
        facts = {"decode_ticks_per_dispatch": eng.decode_ticks_per_dispatch,
                 "page_size": eng.page_size, "page_bytes": eng._page_bytes}
        srv.shutdown()
        srv.server_close()
        eng.close()
    ctx.read_memory_peak()
    del eng, srv
    gc.collect()

    kind = importlib.import_module("benchmark.traffic." + mix["kind"])
    red = serve_metrics.reduce(result["records"], t0, t_end, kind.WINDOW_BY,
                               result["unfinished_threads"])
    samples = red["samples"]
    truncated = sum(r["status"] == "truncated" for r in result["records"])
    ctx.say({"requests": {"attempted": red["attempted"],
                          "failed": red["failed"],
                          "statuses": red["statuses"],
                          "truncated": truncated,
                          "tokens_inside": red["tokens_completed"]},
             "programs_compiled_inside_window": compiled_inside,
             "drain_seconds": round(drained_s, 1), "engine_health": health,
             "device_errors_inside": after["device_errors"]
             - before["device_errors"],
             "moe_pairs_inside": after["moe_pairs"] - before["moe_pairs"],
             "pages_released_by_group": released,
             "engine_step_ms": {"p50": round(steps[len(steps) // 2] * 1e3, 1),
                                "slowest": [round(x * 1e3, 1)
                                            for x in steps[-3:]]},
             "engine_stalls_over_0.5s": stalls})
    for name in ("ttft_ms", "tpot_ms", "front_overhead_ms"):
        ctx.say({"samples": name, **stats.summarize(samples[name]),
                 "beyond_p95": stats.tail_support(len(samples[name]), 95)})
    ctx.say({"generator_lateness_ms": stats.summarize(samples["lateness_ms"]),
             "loop": mix["kind"]})

    check = check_served(ctx, params, d, red["ok"], kind, ctx.check)
    facts.update(before=before, after=after, samples=samples,
                 tokens_completed=red["tokens_completed"],
                 compiled_inside=compiled_inside, dims=d,
                 window_s=red["window_s"])
    return {"attempted": red["attempted"], "failed": red["failed"],
            "end_to_end": serve_metrics.end_to_end(red), "facts": facts,
            "correct": check["correct"] and health == "healthy"
            and not truncated}


def check_served(ctx, params, d, ok: list, kind, spec: dict) -> dict:
    """As ``serve.check_served``, against ``reference/laguna_swa.py``:
    teacher-force a seeded sample of the window's finished requests, the
    longest among them, and read how far each served token's logit lies
    below the reference's best."""
    import jax
    import numpy as np
    from ..reference import laguna_swa
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
    got = jax.device_get(laguna_swa.served_gaps(
        params, ids, first, count, served, d, quant))
    mask = got["mask"]
    gaps = got["gap"][mask]
    worst = float(gaps.max())
    miss = gaps > 0
    line = {"check": "served tokens against the float32 reference",
            "requests": len(chosen), "served_tokens": int(mask.sum()),
            "longest_tokens": longest["n_prompt"]
            + len(longest["output_ids"]),
            "argmax_share": float(1.0 - miss.mean()),
            "mean_gap_where_not_argmax": float(gaps[miss].mean())
            if miss.any() else 0.0,
            "worst_gap": worst, "limit": spec["worst_gap_limit"],
            "distinct_served_tokens": int(len(np.unique(served[mask]))),
            "reference_seconds": round(time.monotonic() - t_ref, 2)}
    if quant:
        cg = got["control_gap"][mask]
        line["control"] = {"quant": quant, "worst_gap": float(cg.max()),
                           "argmax_share": float((cg == 0).mean())}
    ctx.say(line)
    return {"correct": bool(worst <= spec["worst_gap_limit"])}
