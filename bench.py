"""Benchmark harness — prints ONE JSON line for the driver.

Covers the operative BASELINE.md configs on the TPU chip this process
owns. Without a TPU it exits non-zero and prints no result; ``--rehearse``
runs toy sizes on whatever jax finds, to check the control flow, and what
it prints is named a rehearsal, never a device metric:

  - GPT-2-small causal-LM training  (BASELINE config 4 family; headline)
  - ResNet-50 ImageNet-shape training (BASELINE config 2)
  - BERT-base pretraining            (BASELINE config 3)

Each sub-benchmark reports throughput AND MFU (model FLOPs per second /
chip bf16 peak), so the number carries its own context. The measured
step is the same compiled step `paddle_tpu.Model.fit` runs — framework
end-to-end, not a kernel in isolation. Timing loops enqueue steps
asynchronously and block once on the final result (the trainer no longer
syncs per step).

FLOPs accounting (standard MFU conventions, PaLM appendix B):
  transformer train FLOPs/token = 6*N_params + attention term
    (causal GPT: 6*L*s*H; bidirectional BERT: 12*L*s*H)
  resnet: 3x forward FLOPs, forward measured analytically per conv.
"""

from __future__ import annotations

import json
import sys
import time
from typing import Optional

import numpy as np


def _ledger_append(workload: str, value: float, unit: str, **kw):
    """Append the canonical trajectory row (tools/bench_ledger.py).
    Best-effort by contract: the measurement already printed; a ledger
    hiccup must never cost the driver its line. Every row also carries
    the time ledger's goodput verdict on the run (absent when that
    ledger is off — old-schema tolerance)."""
    try:
        from tools import bench_ledger
        for k, v in bench_ledger.goodput_row_fields().items():
            kw.setdefault(k, v)
        bench_ledger.append("bench", workload, value, unit, **kw)
    except Exception as e:  # noqa: BLE001
        print(f"bench: ledger append failed: {e}", file=sys.stderr)

def chip_peak_flops():
    """bf16 peak FLOP/s of the attached chip, or None (CPU/unknown —
    mfu reads null). One table for the whole repo: the live roofline
    gauges and the bench MFU column must agree on the denominator
    (observability/perf.py PEAK_TABLE; FLAGS.perf_peak_flops
    overrides both)."""
    import jax
    from paddle_tpu.core import flags as _flags
    from paddle_tpu.observability.perf import peak_flops_for
    override = float(_flags.get_flag("perf_peak_flops") or 0.0)
    if override > 0:
        return override
    d = jax.devices()[0]
    return peak_flops_for(getattr(d, "device_kind", ""))


def param_count(net) -> int:
    from paddle_tpu.nn.layer import split_state
    params, _ = split_state(net)
    return int(sum(np.prod(v.shape) for v in params.values()))


def _device_feed(feed):
    """Pre-place the synthetic batch on device and force arrival.

    The input pipeline is benchmarked separately (io tests); feeding
    host arrays here would measure the host→device link, not the
    training step."""
    import jax
    placed = jax.tree_util.tree_map(
        lambda x: jax.device_put(np.asarray(x)), feed)
    return jax.block_until_ready(placed)


def _timed_steps(model, feed, warmup: int, iters: int) -> float:
    """Warmup, then time `iters` chained steps. The device queue is
    drained by fetching the final loss to host inside the timed region."""
    feed = _device_feed(feed)
    logs = None
    for _ in range(warmup):
        logs = model.train_batch(*feed)
    float(np.asarray(logs["loss"]))  # true sync
    t0 = time.perf_counter()
    for _ in range(iters):
        logs = model.train_batch(*feed)
    val = np.asarray(logs["loss"])   # true sync, inside the timing
    dt = time.perf_counter() - t0
    assert np.isfinite(val), logs
    return dt


def _mfu(model_flops_per_sec) -> float | None:
    peak = chip_peak_flops()
    if peak is None or model_flops_per_sec is None:
        return None
    return round(model_flops_per_sec / peak, 4)


# ---------------------------------------------------------------------------
# config 4 family: GPT-2-small (headline)
# ---------------------------------------------------------------------------

def bench_gpt(batch: int = 8, seq: int = 1024, warmup: int = 3,
              iters: int = 20, cpu_smoke: bool = False,
              model_name: str = "gpt2-small", fused: bool = True,
              scan_layers: bool = False, remat: bool = False,
              optimizer: str = "adamw", param_dtype: str = None):
    import paddle_tpu as paddle
    from paddle_tpu.models.gpt import (GPTForCausalLM,
                                       GPTFusedPretrainingCriterion,
                                       GPTPretrainingCriterion,
                                       gpt_config)

    paddle.seed(0)
    # fused vocab path: loss streams over vocab chunks, [b,s,V] logits
    # never hit HBM (ops/fused_xent.py; equality with the dense path is
    # asserted in tests/test_fused_xent.py); fused=False measures the
    # dense-logits path for the ± comparison
    if cpu_smoke:
        cfg = gpt_config("gpt2-small", num_layers=2, hidden_size=256,
                         num_heads=4, max_position_embeddings=seq,
                         hidden_dropout=0.0, attention_dropout=0.0,
                         fused_loss=True)
        batch, iters = 2, 5
    else:
        cfg = gpt_config(model_name, max_position_embeddings=seq,
                         hidden_dropout=0.0, attention_dropout=0.0,
                         fused_loss=fused, scan_layers=scan_layers,
                         remat=remat)
    import contextlib
    if param_dtype:
        # the single-chip 1.5B recipe needs bf16 PARAM STORAGE
        # (FEASIBILITY_XL.json: fp32 params+grads alone overflow 16 GiB);
        # scoped so a later bench in this process builds fp32 again
        from paddle_tpu.core.dtype import default_dtype_guard
        guard = default_dtype_guard(param_dtype)
    else:
        guard = contextlib.nullcontext()
    with guard:
        net = GPTForCausalLM(cfg)
    model = paddle.Model(net)
    if optimizer == "adafactor":
        # the single-chip big-model configuration: factored second
        # moments keep optimizer state ~0 bytes/param vs AdamW's 8,
        # which is what lets GPT-2-XL (1.56B) train on one 16 GB chip
        opt = paddle.optimizer.Adafactor(
            learning_rate=1e-4, parameters=net,
            multi_precision=param_dtype is None)
    elif optimizer == "adamw":
        opt = paddle.optimizer.AdamW(learning_rate=1e-4, parameters=net,
                                     weight_decay=0.01)
    else:  # a typo must not stamp a wrong optimizer into the record
        raise ValueError(f"unknown optimizer {optimizer!r}")
    model.prepare(
        optimizer=opt,
        loss=(GPTFusedPretrainingCriterion() if cfg.fused_loss
              else GPTPretrainingCriterion()),
        amp_configs="O1")
    n_params = param_count(net)

    rng = np.random.RandomState(0)
    ids = rng.randint(0, cfg.vocab_size, (batch, seq))
    dt = _timed_steps(model, ([ids], [ids]), warmup, iters)
    tps = batch * seq * iters / dt
    # causal attention: 6*L*s*H train FLOPs per token
    flops_per_token = 6 * n_params + \
        6 * cfg.num_layers * seq * cfg.hidden_size
    return {"metric": "gpt2s_train_tokens_per_sec",
            "value": round(tps, 1), "unit": "tokens/sec",
            "batch": batch, "seq": seq, "params": n_params,
            "model": model_name, "fused": cfg.fused_loss,
            "scan": cfg.scan_layers, "remat": cfg.remat,
            "optimizer": optimizer, "param_dtype": param_dtype or "float32",
            "mfu": _mfu(tps * flops_per_token)}


def bench_steps_per_loop(ks=(1, 8, 32), cpu_smoke: bool = False):
    """Dispatch-overhead sweep (ISSUE 3 / PERF.md "dispatch overhead"):
    the SAME train step run K optimizer steps per XLA dispatch through
    the fused lax.scan loop (`Model.train_loop_batch`). K=1 pays one
    Python→XLA dispatch + one prefetch handoff per step; K>1 amortizes
    both across the slab. Losses are bit-identical across K (pinned by
    tests/test_train_loop.py), so the per-step wall-time delta IS the
    dispatch overhead. Feed is pre-placed on device (`_device_feed`),
    warmup slab excluded (compile), final loss fetched inside the timed
    region (true sync)."""
    import jax
    import paddle_tpu as paddle
    from paddle_tpu.models.gpt import (GPTForCausalLM,
                                       GPTFusedPretrainingCriterion,
                                       gpt_config)

    if cpu_smoke:
        # seq 64 stays under the flash-kernel block threshold: the XLA
        # attention path keeps the step itself cheap, so the per-step
        # delta is dominated by what this sweep measures — dispatch
        batch, seq, total_steps = 2, 64, 32
        cfg_kw = dict(num_layers=2, hidden_size=256, num_heads=4)
    else:
        batch, seq, total_steps = 8, 1024, 32
        cfg_kw = {}
    from paddle_tpu.observability import tracing
    rs = np.random.RandomState(0)
    rows = []
    for k in ks:
        n = total_steps - (total_steps % k)
        if n == 0:
            continue
        paddle.seed(0)
        cfg = gpt_config("gpt2-small", max_position_embeddings=seq,
                         hidden_dropout=0.0, attention_dropout=0.0,
                         fused_loss=True, **cfg_kw)
        net = GPTForCausalLM(cfg)
        model = paddle.Model(net)
        model.prepare(
            optimizer=paddle.optimizer.AdamW(learning_rate=1e-4,
                                             parameters=net,
                                             weight_decay=0.01),
            loss=GPTFusedPretrainingCriterion(), amp_configs="O1")
        ids = rs.randint(0, cfg.vocab_size, (batch, seq))
        # tracing ON for the timed region (span bookkeeping is a few
        # host dict ops per DISPATCH — noise against the XLA step) so
        # the row says where wall time went, not just the total
        tracing.clear()
        tracing.enable()
        if k == 1:
            feed = _device_feed(([ids], [ids]))
            logs = model.train_batch(*feed)          # warmup + compile
            float(np.asarray(logs["loss"]))
            tracing.clear()                          # drop the warmup
            t0 = time.perf_counter()
            for _ in range(n):
                logs = model.train_batch(*feed)
            float(np.asarray(logs["loss"]))          # true sync
            dt = time.perf_counter() - t0
        else:
            slab = np.broadcast_to(ids, (k,) + ids.shape).copy()
            feed = _device_feed(([slab], [slab]))
            logs = model.train_loop_batch(*feed)     # warmup + compile
            float(np.asarray(logs[-1]["loss"]))
            tracing.clear()                          # drop the warmup
            t0 = time.perf_counter()
            for _ in range(n // k):
                logs = model.train_loop_batch(*feed)
            float(np.asarray(logs[-1]["loss"]))      # true sync
            dt = time.perf_counter() - t0
        rollup = {name: {"total_s": v["total_s"], "count": v["count"],
                         "share_of_wall": round(v["total_s"] / dt, 4)}
                  for name, v in tracing.rollup(prefix="train.").items()}
        tracing.disable()
        rows.append({"steps_per_loop": k, "steps": n,
                     "per_step_ms": round(dt / n * 1e3, 3),
                     "tokens_per_sec": round(batch * seq * n / dt, 1),
                     "span_rollup": rollup})
    base = next((r for r in rows if r["steps_per_loop"] == 1), None)
    if base:
        for r in rows:
            r["speedup_vs_k1"] = round(
                base["per_step_ms"] / r["per_step_ms"], 3)
    return {"metric": "train_loop_dispatch_sweep", "batch": batch,
            "seq": seq, "rows": rows}


# ---------------------------------------------------------------------------
# config 5: Wide&Deep CTR (sparse embedding + PS-analog host table)
# ---------------------------------------------------------------------------

def bench_widedeep(batch: int = 16384, warmup: int = 3, iters: int = 30,
                   cpu_smoke: bool = False, table: str = "hbm"):
    """Criteo-shape CTR training: 13 dense + 26 categorical slots into a
    shared table, wide+deep towers, BCE loss. ``table="hbm"`` keeps a
    1M-row table on device (pure-SPMD CTR); ``table="host"`` trains
    against a 100M-id HOST-RAM table pulled/pushed per step — the
    parameter-server workload the reference ran on CPU clusters
    (BASELINE config 5). Metric: samples/sec (CTR is lookup/bandwidth
    bound; MFU is not meaningful)."""
    import paddle_tpu as paddle
    from paddle_tpu import nn
    from paddle_tpu.models.widedeep import WideDeep, WideDeepHostTable

    paddle.seed(0)
    if cpu_smoke:
        batch, iters = 256, 3
    if table == "host":
        net = WideDeepHostTable(vocab_size=100 * 1000 * 1000,
                                embedding_dim=16)
    else:
        net = WideDeep(vocab_size=1000 * 1000, embedding_dim=16)
    model = paddle.Model(net)
    model.prepare(
        optimizer=paddle.optimizer.Adam(learning_rate=1e-3,
                                        parameters=net),
        loss=nn.BCEWithLogitsLoss())
    rng = np.random.RandomState(0)
    dense = rng.randn(batch, 13).astype(np.float32)
    # raw 2^31-range ids, hash-folded by the table (the Criteo regime:
    # ids far exceed any dense table range)
    sparse = rng.randint(0, 1 << 31, (batch, 26)).astype(np.int64)
    labels = (rng.rand(batch) < 0.3).astype(np.float32)
    dt = _timed_steps(model, ([dense, sparse], [labels]), warmup, iters)
    sps = batch * iters / dt
    return {"metric": f"widedeep_{table}_train_samples_per_sec",
            "value": round(sps, 1), "unit": "samples/sec",
            "batch": batch, "table": table,
            "lookups_per_sec": round(sps * 26, 1), "mfu": None}


# ---------------------------------------------------------------------------
# LLM decode serving (continuous batching; VERDICT r4 item 4)
# ---------------------------------------------------------------------------

def bench_llm_decode(n_requests: int = 16, max_seqs: int = 8,
                     prompt_len: int = 128, gen_len: int = 128,
                     cpu_smoke: bool = False,
                     model_name: str = "gpt2-small"):
    """Multi-client decode throughput through LLMEngine: n_requests
    greedy generations (prompt_len ctx, gen_len new tokens) share one
    engine with max_seqs slots. Metrics: aggregate generated tokens/sec
    (the serving headline), mean per-request latency, mean TTFT."""
    import paddle_tpu as paddle
    from paddle_tpu.inference.llm import LLMEngine
    from paddle_tpu.models.gpt import GPTForCausalLM, gpt_config

    paddle.seed(0)
    if cpu_smoke:
        cfg = gpt_config("gpt2-small", num_layers=2, hidden_size=128,
                         num_heads=4, vocab_size=503,
                         max_position_embeddings=256,
                         hidden_dropout=0.0, attention_dropout=0.0)
        n_requests, prompt_len, gen_len = 4, 16, 16
    else:
        cfg = gpt_config(model_name, hidden_dropout=0.0,
                         attention_dropout=0.0)
    from paddle_tpu.observability import tracing
    net = GPTForCausalLM(cfg)
    total = prompt_len + gen_len
    pages = -(-total // 16) * max_seqs + 8
    rng = np.random.RandomState(0)
    prompts = [rng.randint(0, cfg.vocab_size, prompt_len).tolist()
               for _ in range(n_requests)]
    with LLMEngine(net, max_seqs=max_seqs, page_size=16,
                   num_pages=pages, max_len=total,
                   prefill_chunk=prompt_len) as eng:
        # warmup compiles prefill + decode
        eng.generate([prompts[0]], max_new_tokens=2)
        tracing.clear()
        tracing.enable()           # per-phase rollup for the BENCH row
        t0 = time.perf_counter()
        futs = [eng.submit(p, max_new_tokens=gen_len) for p in prompts]
        outs = [f.result() for f in futs]
        dt = time.perf_counter() - t0
    # phases tile llm.request, so excluding the root gives shares
    # over where each request's wall time actually went
    rollup = tracing.rollup(prefix="llm.", exclude=("llm.request",))
    tracing.disable()
    gen_tokens = sum(len(o["output_ids"]) for o in outs)
    assert not any(o["truncated"] for o in outs)
    return {"metric": "llm_decode_tokens_per_sec",
            "value": round(gen_tokens / dt, 1), "unit": "tokens/sec",
            "model": model_name, "n_requests": n_requests,
            "max_seqs": max_seqs, "prompt_len": prompt_len,
            "gen_len": gen_len,
            "mean_latency_s": round(float(np.mean(
                [o["latency_s"] for o in outs])), 3),
            "mean_ttft_s": round(float(np.mean(
                [o["ttft_s"] for o in outs])), 3),
            "span_rollup": rollup,
            "mfu": None}


# ---------------------------------------------------------------------------
# config 2: ResNet-50 ImageNet-shape
# ---------------------------------------------------------------------------

RESNET50_FWD_FLOPS = 4.09e9   # per 224x224 image, 2*MACs convention


def bench_resnet(batch: int = 128, warmup: int = 3, iters: int = 30,
                 cpu_smoke: bool = False):
    import paddle_tpu as paddle
    from paddle_tpu import nn
    from paddle_tpu.models.resnet import resnet50

    paddle.seed(0)
    size = 32 if cpu_smoke else 224
    if cpu_smoke:
        batch, iters = 4, 3
    net = resnet50()
    model = paddle.Model(net)
    model.prepare(
        optimizer=paddle.optimizer.Momentum(learning_rate=0.1, momentum=0.9,
                                            parameters=net),
        loss=nn.CrossEntropyLoss(),
        amp_configs="O1")
    rng = np.random.RandomState(0)
    imgs = rng.randn(batch, 3, size, size).astype(np.float32)
    labels = rng.randint(0, 1000, (batch, 1))
    dt = _timed_steps(model, ([imgs], [labels]), warmup, iters)
    ips = batch * iters / dt
    flops_per_img = 3 * RESNET50_FWD_FLOPS * (size / 224.0) ** 2
    return {"metric": "resnet50_train_images_per_sec",
            "value": round(ips, 1), "unit": "images/sec",
            "batch": batch, "image_size": size,
            "mfu": _mfu(ips * flops_per_img) if size == 224 else None}


# ---------------------------------------------------------------------------
# config 3: BERT-base pretraining
# ---------------------------------------------------------------------------

def bench_bert(batch: int = 64, seq: int = 128, warmup: int = 3,
               iters: int = 30, cpu_smoke: bool = False,
               scan_layers: bool = False, remat: bool = False):
    import paddle_tpu as paddle
    from paddle_tpu.models.bert import (BertForPretraining,
                                        BertFusedPretrainingCriterion,
                                        bert_config)

    paddle.seed(0)
    if cpu_smoke:
        cfg = bert_config("bert-base", num_layers=2, hidden_size=128,
                          num_heads=2, hidden_dropout=0.0,
                          attention_dropout=0.0, fused_loss=True)
        batch, iters = 2, 3
    else:
        cfg = bert_config("bert-base", hidden_dropout=0.0,
                          attention_dropout=0.0, fused_loss=True,
                          scan_layers=scan_layers, remat=remat)
    net = BertForPretraining(cfg)
    model = paddle.Model(net)
    model.prepare(
        optimizer=paddle.optimizer.AdamW(learning_rate=1e-4, parameters=net,
                                         weight_decay=0.01),
        loss=BertFusedPretrainingCriterion(),
        amp_configs="O1")
    n_params = param_count(net)
    rng = np.random.RandomState(0)
    ids = rng.randint(0, cfg.vocab_size, (batch, seq))
    mlm_labels = np.where(rng.rand(batch, seq) < 0.15, ids, -100)
    nsp = rng.randint(0, 2, (batch,))

    dt = _timed_steps(model, ([ids], [mlm_labels, nsp]), warmup, iters)
    sps = batch * iters / dt
    flops_per_token = 6 * n_params + \
        12 * cfg.num_layers * seq * cfg.hidden_size
    return {"metric": "bertbase_train_samples_per_sec",
            "value": round(sps, 1), "unit": "samples/sec",
            "batch": batch, "seq": seq, "params": n_params,
            "scan": cfg.scan_layers, "remat": cfg.remat,
            "mfu": _mfu(sps * seq * flops_per_token)}


def _device_or_exit(rehearse: bool) -> dict:
    """The device this process measures on, as jax reports it. A
    measurement path that finds no TPU fails (exit 2): there is no CPU
    result. ``rehearse`` (the explicit ``--rehearse`` argument, nothing
    falls back to it) runs the tiny sizes on whatever jax finds, to
    check the control flow."""
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu" and not rehearse:
        print(f"bench: jax found platform {dev.platform!r}, not 'tpu' — "
              f"bench.py measures on the chip or fails "
              f"(--rehearse checks the control flow at toy sizes)",
              file=sys.stderr)
        raise SystemExit(2)
    from paddle_tpu.core import compile_cache
    compile_cache.enable()
    return {"platform": dev.platform, "device_kind": dev.device_kind,
            "device_count": len(jax.devices())}


def main(rehearse: bool = False):
    """Measure in THIS process — the one that owns the chip — and print
    one JSON line. Exits non-zero when the platform is not ``tpu`` and
    when any sub-benchmark raises."""
    device = _device_or_exit(rehearse)
    extra = {}
    for name, fn in (("resnet50", bench_resnet), ("bert", bench_bert),
                     ("widedeep", bench_widedeep)):
        extra[name] = fn(cpu_smoke=rehearse)

    if rehearse:
        # a rehearsal's numbers are not device metrics: every name says
        # so, and nothing goes to the ledger
        extra["gpt"] = bench_gpt(cpu_smoke=True)
        for rec in extra.values():
            rec["metric"] = "rehearsal_" + rec["metric"]
        print(json.dumps({"metric": "rehearsal_toy_sizes", "value": 0.0,
                          "unit": "none", "rehearsal": True, **device,
                          "extra": extra}))
        return
    # batch is NOT monotone in throughput on this chip (PERF.md: the
    # fused vocab path's HBM traffic grows with batch), so time each
    # candidate and report the best; OOM just drops a candidate
    gpt = None
    last_msg = None
    for b in (8, 16, 32):
        try:
            cand = bench_gpt(batch=b)
        except Exception as e:  # noqa: BLE001
            msg = str(e)
            if "RESOURCE_EXHAUSTED" not in msg and \
                    "out of memory" not in msg.lower():
                raise
            # drop the exception (its traceback pins the failed
            # attempt's on-device buffers) before retrying
            last_msg = msg[:300]
            del e
            print(f"bench gpt batch {b} OOM; skipping", file=sys.stderr)
            continue
        if gpt is None or cand["value"] > gpt["value"]:
            gpt = cand
    if gpt is None:
        raise RuntimeError(f"all gpt batches OOMed: {last_msg}")
    metric = "gpt2s_train_tokens_per_sec"
    print(json.dumps({"metric": metric, "value": gpt["value"],
                      "unit": "tokens/sec", "mfu": gpt.get("mfu"),
                      **device, "extra": extra}))
    _ledger_append(metric, gpt["value"], "tokens/sec",
                   tokens_per_sec=gpt["value"], mfu=gpt.get("mfu"),
                   backend=device["device_kind"],
                   extra={"batch": gpt.get("batch"),
                          "model": gpt.get("model")})


def _steps_per_loop_cli(rehearse: bool):
    """`python bench.py --steps-per-loop [1,8,32]`: the fused-loop
    dispatch-overhead sweep, on the chip (or, with ``--rehearse``, at
    toy sizes on whatever jax finds); prints one JSON line."""
    i = sys.argv.index("--steps-per-loop")
    ks = (1, 8, 32)
    if len(sys.argv) > i + 1 and not sys.argv[i + 1].startswith("-"):
        ks = tuple(int(v) for v in sys.argv[i + 1].split(","))
    device = _device_or_exit(rehearse)
    rec = bench_steps_per_loop(ks=ks, cpu_smoke=rehearse)
    rec.update(device, rehearsal=rehearse)
    if rehearse:
        rec["metric"] = "rehearsal_" + rec["metric"]
    print(json.dumps(rec))
    sys.stdout.flush()
    if rehearse:
        return   # not a device metric: nothing goes to the ledger
    best = max(rec["rows"], key=lambda r: r["tokens_per_sec"])
    _ledger_append("train_loop_dispatch_sweep",
                   best["tokens_per_sec"], "tokens/sec",
                   tokens_per_sec=best["tokens_per_sec"],
                   backend=device["device_kind"],
                   extra={"steps_per_loop": best["steps_per_loop"],
                          "speedup_vs_k1": best.get("speedup_vs_k1"),
                          "ks": [r["steps_per_loop"]
                                 for r in rec["rows"]]})


if __name__ == "__main__":
    _rehearse = "--rehearse" in sys.argv
    if "--steps-per-loop" in sys.argv:
        _steps_per_loop_cli(_rehearse)
    else:
        main(_rehearse)
