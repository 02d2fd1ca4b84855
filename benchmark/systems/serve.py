"""The serving system under test: ``LLMEngine`` behind ``serve_llm``, driven
over HTTP by the load generator child."""

from __future__ import annotations

import gc
import importlib
import json
import os
import subprocess
import sys
import threading
import time

from .. import serve_metrics, stats, weights

HBM_SHARE = 0.90        # of bytes_limit, as chip_smoke.py sized it (PR 21)
TEMP_MARGIN = 1.25


def build_net(model: dict, params: dict, **cfg_kw):
    """The program's network around the benchmark's arrays. The constructor's
    own initialisers run under ``eval_shape`` (nothing is computed); every
    leaf is then replaced by the array made from the seed."""
    import jax
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
    cfg = GPTConfig(
        vocab_size=model["vocab_size"], hidden_size=model["hidden_size"],
        num_layers=model["num_layers"], num_heads=model["num_heads"],
        ffn_hidden_size=model["ffn_hidden_size"],
        max_position_embeddings=model["max_position_embeddings"],
        layer_norm_epsilon=model.get("layer_norm_epsilon", 1e-5),
        hidden_dropout=0.0, attention_dropout=0.0, **cfg_kw)
    box = {}

    def construct():
        box["net"] = GPTForCausalLM(cfg)
        return 0

    jax.eval_shape(construct)
    # the initialisers drew traced keys from the program's global stream:
    # give it a concrete state again
    import paddle_tpu as pt
    pt.seed(0)
    net = box["net"]
    net.set_state_dict(params)
    return net


def plan_pages(model: dict, eng: dict, limit: int, in_use: int) -> dict:
    """How many KV pages fit: what is left of HBM_SHARE of the device after
    the weights and the temporaries of the widest engine program. The default
    attention path gathers ``max_len`` of K and V in float32 for every query
    row of a mixed tick (chunk rows + decode rows)."""
    kv = model["num_heads"] * model["head_dim"]
    rows = eng["prefill_chunk"] + eng["max_seqs"]
    temp = int(rows * eng["max_len"] * kv * 4 * 2 * TEMP_MARGIN)
    kv_bytes = {"bf16": 2, "f16": 2, "f32": 4, "int8": 1}[eng["kv_dtype"]]
    page = model["num_layers"] * eng["page_size"] * kv * 2 * kv_bytes
    pages = int((limit * HBM_SHARE - in_use - temp) // page)
    return {"num_pages": pages, "planned_temp_bytes": temp,
            "page_bytes": page, "pool_bytes": pages * page}


def _counter(name: str) -> float:
    from paddle_tpu.observability import metrics as obs
    fam = obs.default_registry().get(name)
    return 0.0 if fam is None else float(fam.value)


def _queue_wait_buckets():
    from paddle_tpu.observability import metrics as obs
    fam = obs.default_registry().get("llm_queue_wait_seconds")
    return None if fam is None else list(fam.bucket_counts())


def _snapshot(eng) -> dict:
    return {"n_host_dispatches": eng.n_host_dispatches,
            "n_prompt_tokens": eng.n_prompt_tokens,
            "n_cached_tokens": eng.n_cached_tokens,
            "queue_wait": _queue_wait_buckets(),
            "device_errors": _counter("llm_device_errors_total")}


def _sleep_until(t: float):
    while True:
        left = t - time.monotonic()
        if left <= 0:
            return
        time.sleep(min(left, 0.05))


def run(ctx) -> dict:
    import jax
    import jax.numpy as jnp
    from paddle_tpu.inference.llm import LLMEngine, serve_llm

    cfg, mix = ctx.config, ctx.workload
    d = weights.dims_of(cfg)
    dtype = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[
        cfg["precision"]["weights"]]
    params = weights.make(d, ctx.seed, dtype)
    jax.block_until_ready(params)
    net = build_net(cfg, params, use_flash=False)
    net.eval()
    ctx.mark("weights")

    eng_cfg = dict(cfg["engine"])
    if "num_pages" not in eng_cfg:
        st = jax.local_devices()[0].memory_stats()
        plan = plan_pages(cfg, eng_cfg, int(st["bytes_limit"]),
                          int(st["bytes_in_use"]))
        eng_cfg["num_pages"] = plan["num_pages"]
        ctx.say({"sizing": plan, "bytes_limit": int(st["bytes_limit"]),
                 "weights_bytes_in_use": int(st["bytes_in_use"])})
    eng = LLMEngine(net, **eng_cfg)
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
        before = _snapshot(eng)
        if ctx.trace:
            # the middle of the window, long enough to hold every program
            # the traffic drives whatever the queue does (``trace_s``)
            span = min(float(mix.get("trace_s", 5.0)), ctx.seconds * 0.5)
            ctx.trace_between(t0 + (ctx.seconds - span) / 2,
                              t0 + (ctx.seconds + span) / 2)
        _sleep_until(t_end)
        after = _snapshot(eng)
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
        facts = {"decode_ticks_per_dispatch": eng.decode_ticks_per_dispatch}
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
    ctx.say({"requests": {"attempted": red["attempted"],
                          "failed": red["failed"],
                          "statuses": red["statuses"],
                          "tokens_inside": red["tokens_completed"]},
             "programs_compiled_inside_window": compiled_inside,
             "drain_seconds": round(drained_s, 1), "engine_health": health,
             "device_errors_inside": after["device_errors"]
             - before["device_errors"]})
    for name in ("ttft_ms", "tpot_ms", "front_overhead_ms"):
        ctx.say({"samples": name, **stats.summarize(samples[name]),
                 "beyond_p95": stats.tail_support(len(samples[name]), 95)})
    ctx.say({"per_request": {
        key: [round(x, 1) if isinstance(x, float) else x for x in col]
        for key, col in (
            ("n_prompt", [r["n_prompt"] for r in red["ok"]]),
            ("n_out", [len(r["output_ids"]) for r in red["ok"]]),
            ("ttft_ms", samples["ttft_ms"]),
            ("front_overhead_ms", samples["front_overhead_ms"]))}})
    ctx.say({"generator_lateness_ms": stats.summarize(samples["lateness_ms"]),
             "loop": mix["kind"]})

    check = check_served(ctx, params, d, red["ok"], kind, ctx.check)
    facts.update(before=before, after=after, samples=samples,
                 tokens_completed=red["tokens_completed"],
                 compiled_inside=compiled_inside, dims=d,
                 window_s=red["window_s"])
    return {"attempted": red["attempted"], "failed": red["failed"],
            "end_to_end": serve_metrics.end_to_end(red), "facts": facts,
            "correct": check["correct"] and health == "healthy"}


def check_served(ctx, params, d, ok: list, kind, spec: dict) -> dict:
    """Teacher-force a seeded sample of the window's finished requests, the
    longest among them, through the float32 reference and read how far each
    served token's logit lies below the reference's best."""
    import jax
    import numpy as np
    from ..reference import gpt_dense
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
    prompts = [again[r["index"]] for r in chosen]
    pad = int(spec["pad_to"])
    ids = np.zeros((len(chosen), pad), np.int32)
    served = np.zeros((len(chosen), pad), np.int32)
    first = np.zeros(len(chosen), np.int32)
    count = np.zeros(len(chosen), np.int32)
    for b, (r, prompt) in enumerate(zip(chosen, prompts)):
        out = r["output_ids"]
        if len(prompt) != r["n_prompt"]:
            raise RuntimeError("a regenerated prompt has another length")
        seq = list(prompt) + list(out)
        ids[b, :len(seq)] = seq
        first[b] = len(prompt) - 1
        count[b] = len(out)
        served[b, len(prompt) - 1:len(seq) - 1] = out
    quant = spec["control"] if ctx.control else None
    got = jax.device_get(gpt_dense.served_gaps(
        params, ids, first, count, served, d, quant))
    mask = got["mask"]
    gaps = got["gap"][mask]
    n = int(mask.sum())
    worst = float(gaps.max())
    miss = gaps > 0
    line = {"check": "served tokens against the float32 reference",
            "requests": len(chosen), "served_tokens": n,
            "argmax_share": float(1.0 - miss.mean()),
            "mean_gap_where_not_argmax": float(gaps[miss].mean())
            if miss.any() else 0.0,
            "worst_gap": worst, "limit": spec["worst_gap_limit"],
            "reference_seconds": round(time.monotonic() - t_ref, 2)}
    if quant:
        cg = got["control_gap"][mask]
        line["control"] = {"quant": quant, "worst_gap": float(cg.max()),
                           "argmax_share": float((cg == 0).mean())}
    ctx.say(line)
    return {"correct": bool(worst <= spec["worst_gap_limit"])}
