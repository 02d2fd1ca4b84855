"""The looped serving system under test: ``OuroForCausalLM`` (one stack run
``total_ut_steps`` times a token) in ``LLMEngine`` behind ``serve_llm``,
driven over HTTP by the load generator child. The run is
``systems/serve.py``'s, with this configuration's network, weights
(``weights_looped.py``), page plan and reference
(``reference/ouro_looped.py``) in GPT's place: ``serve.run`` names those
itself, so its body is repeated here, as in ``serve_hybrid.py``, until a
``benchmark`` issue folds the three (ROADMAP A0b(g))."""

from __future__ import annotations

import gc
import importlib
import json
import os
import subprocess
import sys
import threading
import time

from .. import roofline_looped, serve_metrics, stats, weights_looped
from .serve import _counter, _sleep_until, _snapshot
from .serve_hybrid import _StallWatch

KV_BYTES = {"bf16": 2, "f16": 2, "f32": 4}
NEAR_END = 10       # tokens before a request's last at which a trace starts


def build_net(model: dict, params: dict):
    """The program's network around the benchmark's arrays (the constructor's
    own initialisers run under ``eval_shape``: nothing is computed)."""
    import jax
    from paddle_tpu.models.ouro import OuroConfig, OuroForCausalLM
    keys = ("vocab_size", "hidden_size", "num_hidden_layers",
            "num_attention_heads", "num_key_value_heads", "head_dim",
            "intermediate_size", "rms_norm_eps", "rope_theta",
            "max_position_embeddings", "total_ut_steps",
            "early_exit_threshold")
    cfg = OuroConfig(**{k: model[k] for k in keys})
    box = {}

    def construct():
        box["net"] = OuroForCausalLM(cfg)
        return 0

    jax.eval_shape(construct)
    import paddle_tpu as pt
    pt.seed(0)
    net = box["net"]
    net.set_state_dict(params)
    return net


def plan_pages(d: dict, eng: dict, pool: dict, limit: int,
               in_use: int) -> dict:
    """How many K/V pages fit: ``hbm_share`` of the device less what is in
    use (the weights) less ``reserve_bytes`` (the programs' temporaries,
    the compiler's scratch, the host's transfers), over the bytes of a page
    across ALL ``layers x passes`` cache layers. Fewer than ``min_pages``
    (every slot's longest sequence + the scratch page) fails the run."""
    page = int(roofline_looped.page_bytes(
        d, eng["page_size"], KV_BYTES[eng["kv_dtype"]]))
    pages = int((limit * pool["hbm_share"] - in_use
                 - pool["reserve_bytes"]) // page)
    plan = {"num_pages": pages, "page_bytes": page,
            "pool_bytes": pages * page,
            "reserve_bytes": int(pool["reserve_bytes"]),
            "min_pages": int(pool["min_pages"])}
    if pages < pool["min_pages"]:
        raise RuntimeError(f"the K/V pool would hold {pages} pages, under "
                           f"the {pool['min_pages']} the cell needs: {plan}")
    return plan


def _trace_a_turnover(ctx, eng, t_from: float, t_latest: float,
                      span: float) -> threading.Thread:
    """Trace ``span`` seconds that hold BOTH programs. A tick of this model
    is ~30,000 device events (192 layer applications), and the profiler
    keeps about a million: a trace holds ~30 ticks, where a caller's
    request ends, and the next one's prompt rides a mixed tick, once in
    ~35. So the trace starts, from ``t_from`` on, when some live request
    is within ``NEAR_END`` tokens of its last (by ``t_latest`` whatever
    the slots hold): its successor's ``mixed_fn`` then falls inside."""
    def near_end():
        return any(r is not None and r.max_new_tokens - len(r.tokens)
                   <= NEAR_END for r in list(eng._slots))

    def body():
        _sleep_until(t_from)
        while time.monotonic() < t_latest and not near_end():
            time.sleep(0.005)
        ctx.start_trace()
        time.sleep(span)
        ctx.stop_trace()

    tracer = threading.Thread(target=body, daemon=True)
    tracer.start()
    return tracer


def _snapshot_looped(eng) -> dict:
    snap = _snapshot(eng)
    snap.update(n_tokens=eng.n_tokens,
                loop_steps=_counter("llm_loop_steps_total"),
                loop_exit_step_rows=eng.loop_exit_step_rows.tolist())
    return snap


def run(ctx) -> dict:
    import jax
    import jax.numpy as jnp
    # a program without the model (a parent commit) fails here, at once
    import paddle_tpu.models.ouro  # noqa: F401
    from paddle_tpu.inference.llm import LLMEngine, serve_llm

    cfg, mix = ctx.config, ctx.workload
    d = weights_looped.dims_of(cfg)
    dtype = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[
        cfg["precision"]["weights"]]
    params = weights_looped.make(d, ctx.seed, dtype)
    jax.block_until_ready(params)
    net = build_net(cfg, params)
    net.eval()
    ctx.mark("weights")

    eng_cfg = dict(cfg["engine"])
    sizing = {"parameters": weights_looped.n_params(d)}
    if "num_pages" not in eng_cfg:
        st = jax.local_devices()[0].memory_stats()
        plan = plan_pages(d, eng_cfg, cfg["pool"], int(st["bytes_limit"]),
                          int(st["bytes_in_use"]))
        eng_cfg["num_pages"] = plan["num_pages"]
        sizing.update(plan, bytes_limit=int(st["bytes_limit"]),
                      weights_bytes_in_use=int(st["bytes_in_use"]))
    eng = LLMEngine(net, **eng_cfg)
    if eng.max_seqs * eng.pages_per_seq > eng.num_pages - 1:
        raise RuntimeError("the pool cannot hold every slot's longest "
                           "sequence: a request could be truncated")
    sizing.update(engine_page_bytes=eng._page_bytes,
                  kv_cache_layers=net.kv_cache_spec()[0])
    ctx.say({"sizing": sizing})
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
        before = _snapshot_looped(eng)
        tracer = None
        if ctx.trace:
            span = min(float(mix.get("trace_s", 1.0)), ctx.seconds * 0.5)
            tracer = _trace_a_turnover(
                ctx, eng, t0 + (ctx.seconds - span) / 2,
                t_end - 2 * span - 5.0, span)
        _sleep_until(t_end)
        after = _snapshot_looped(eng)
        stalls = [dict(st, at=round(st["at"] - t0, 2))
                  for st in watch.stop()]
        steps = sorted(list(eng.step_durations)[
            -max(1, after["n_host_dispatches"]
                 - before["n_host_dispatches"]):])
        compiled_inside = ctx.compile_count() - compiles0
        child.wait(timeout=float(mix.get("drain_s", 120)) + 60)
        drained_s = time.monotonic() - t_end
        if tracer is not None:
            tracer.join()
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
             "loop_exit_step_rows_inside": [
                 a - b for a, b in zip(after["loop_exit_step_rows"],
                                       before["loop_exit_step_rows"])],
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
            "correct": check["correct"] and health == "healthy"}


def check_served(ctx, params, d, ok: list, kind, spec: dict) -> dict:
    """As ``serve.check_served``, against ``reference/ouro_looped.py``:
    teacher-force a seeded sample of the window's finished requests, the
    longest among them, and read how far each served token's logit lies
    below the reference's best; the reference's exit steps of the served
    positions beside it."""
    import jax
    import numpy as np
    from ..reference import ouro_looped
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
    got = jax.device_get(ouro_looped.served_gaps(
        params, ids, first, count, served, d, quant))
    mask = got["mask"]
    gaps = got["gap"][mask]
    worst = float(gaps.max())
    miss = gaps > 0
    line = {"check": "served tokens against the float32 reference",
            "requests": len(chosen), "served_tokens": int(mask.sum()),
            "argmax_share": float(1.0 - miss.mean()),
            "mean_gap_where_not_argmax": float(gaps[miss].mean())
            if miss.any() else 0.0,
            "worst_gap": worst, "limit": spec["worst_gap_limit"],
            "reference_exit_step_rows": np.bincount(
                got["exit_step"][mask], minlength=d["steps"]).tolist(),
            "distinct_served_tokens": int(len(np.unique(served[mask]))),
            "reference_seconds": round(time.monotonic() - t_ref, 2)}
    if quant:
        cg = got["control_gap"][mask]
        line["control"] = {"quant": quant, "worst_gap": float(cg.max()),
                           "argmax_share": float((cg == 0).mean())}
    ctx.say(line)
    return {"correct": bool(worst <= spec["worst_gap_limit"])}
