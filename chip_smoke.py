#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that paddle-tpu still starts on the chip.

    python chip_smoke.py             # one TPU chip (what the driver runs)
    python chip_smoke.py --chips 4   # the sharded trainer on a 4-chip mesh, and
                                     # what it is compared with; nothing else

One process: it imports jax once and owns the chip. It drives the two main
paths through the entry points users call, at the full width of
``gpt3-1.3b`` (hidden 2048, 16 heads x 128, ffn 8192, vocab 50304, context
2048; random weights from ``--seed``):

  device   platform must be ``tpu`` — anything else is an error, never a CPU pass
  kernels  flash attention (fwd + bwd) and the ragged paged-attention kernel
           (a layer of a stacked bf16 and int8 pool, MHA and GQA 16/4),
           compiled (interpret=False), against their jnp references; then
           the kernel's time a call beside the gathered path's at the
           serving benchmark's decode and mixed shapes and at the window /
           full attention cell's two decode calls, the state step's
           kernel beside ``ssd_step`` and the routed experts' grouped
           product beside ``jax.lax.ragged_dot`` at the hybrid cell's shapes,
           the delta rule's chunk form (``ops/kda.py``, plain jax.numpy)
           held to its recurrence and timed at ``reason_closed_kda``'s and
           ``reason_closed_gdn``'s shapes, and its step's kernel beside
           ``kda_step``, whole state arrays held to the equation
  gdn_program  (alone: ``--phase gdn_program``) ``reason_closed_gdn``'s own
           decode program under ``kda_step`` and under the step's kernel,
           the same ticks, every delta-rule layer's state compared
  staging  a decode dispatch's host arrays sent one ``jnp.asarray`` each
           beside the one packed vector of ``inference/staging.py``, timed
           at ``chat_closed``'s and ``mixed_len_closed_sink``'s shapes
  serve    full-depth 1.3B, bf16 weights and KV pool, ``LLMEngine`` behind
           ``serve_llm``; HTTP ``POST /generate`` checked against
           ``net.generate``; once with attention_impl="xla", once "pallas"
  serve_hybrid  the hybrid decoder (Mamba-2 state beside K/V pages, dropless
           routed experts) at granite-4.0-h-small's widths, four layers, 8 of
           72 experts held: served through both ``attention_impl`` values
           and held to ``net.generate``
  serve_looped  the looped decoder (models/ouro.py) at Ouro-2.6B's published
           widths and depth, 48 layers run four times a token over 192 cache
           layers: served over HTTP on the default path, each served token
           held to the float32 reference computed a layer at a time
  train    ``Model.prepare(amp_configs="O1")`` + ``Model.fit`` at 1.3B width,
           flash attention and the fused loss on, AdamW; depth cut to what
           one chip holds (printed as ``reduced``)

Every phase prints one JSON object on its own line. The script exits non-zero
at the first thing that is wrong and prints the ``ok`` line only when every
phase passed. Nothing here is a benchmark: the seconds printed are smoke
readings (compilation included where said).
"""

from __future__ import annotations

import argparse
import gc
import json
import sys
import threading
import time
import urllib.request

MODEL = "gpt3-1.3b"
SEQ = 2048
PAGE = 16
MAX_SEQS = 8
NEW_TOKENS = 64
# The gathered ("xla") path, served here beside the kernel, gathers max_len
# of K and V in f32 for every query row: rows x max_len x kv_heads x d x 4 B,
# twice (K and V), per layer (ops/paged_attention._gathered_attention). At max_len 2048 and 16 x 128
# that is 33.5 MB a row: the TPU compiler's memory analysis of the mixed-tick
# program read 5.3 GiB of temporaries at prefill_chunk 128 (136 rows) — too
# much beside 2.5 GiB of weights and a pool meant to fill the rest — and half
# that at 64, so the smoke serves with prefill_chunk 64 and keeps max_len at
# the model's published 2048.
PREFILL_CHUNK = 64
DECODE_TICKS = 4
# Share of the device's bytes_limit a phase plans to use.
HBM_SHARE = 0.90
# Kernel vs jnp reference, on O(1) values: bf16 inputs, f32 accumulation, and
# a reference whose f32 einsum runs at the TPU's default (bf16-pass) precision.
KERNEL_ATOL = 2e-2
KERNEL_RTOL = 2e-2
# The flash kernel's gradients, each divided by its reference's largest
# magnitude: bf16 gradients of a sum of squares over 2048 keys.
GRAD_ATOL = 4e-2
# Greedy streams: the repo's contract is token identity with net.generate.
# With bf16 weights two programs that sum in a different order can break a
# near-tie; a differing token is admitted only when the reference's own logits
# (teacher-forced on the engine's stream) put it within TIE_TOL of the top
# logit: four bf16 ulps at the top logits' magnitude (4..8 -> ulp 2^-5).
TIE_TOL = 0.125
# One-device vs mesh loss streams of the same model, seed and batch (bf16
# compute, different reduction orders across shards).
MESH_LOSS_RTOL = 1e-2
# First-step loss, flash + fused loss vs the XLA attention + dense logits.
REF_LOSS_RTOL = 1e-2
# No device of the mesh may hold more than this multiple of the mean.
BALANCE = 1.25


class SmokeFailure(Exception):
    pass


def emit(obj) -> None:
    print(json.dumps(obj), flush=True)


def check(cond, msg) -> None:
    if not cond:
        raise SmokeFailure(msg)


def free_device_memory() -> None:
    """Between phases: dropped models and engines release their buffers at
    collection, and jit caches may still hold what they closed over."""
    import jax
    gc.collect()
    jax.clear_caches()
    gc.collect()


def hbm(dev):
    """(bytes_limit, bytes_in_use) of a device."""
    stats = dev.memory_stats() or {}
    check("bytes_limit" in stats, f"{dev} reports no memory_stats()")
    return int(stats["bytes_limit"]), int(stats["bytes_in_use"])


# ---------------------------------------------------------------------------
# device
# ---------------------------------------------------------------------------

_cache_events = {"hits": 0, "misses": 0}


def phase_device(want_count: int) -> dict:
    import jax
    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu":
        raise SmokeFailure(
            f"jax found platform {dev.platform!r}, not 'tpu': chip_smoke "
            f"runs on the chip or fails")
    check(len(devs) == want_count,
          f"expected {want_count} device(s), jax reports {len(devs)}")
    from paddle_tpu.core import compile_cache
    cache_dir, origin = compile_cache.enable()

    def on_event(name, **_):
        if name == "/jax/compilation_cache/cache_hits":
            _cache_events["hits"] += 1
        elif name == "/jax/compilation_cache/cache_misses":
            _cache_events["misses"] += 1

    jax.monitoring.register_event_listener(on_event)
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs)}
    emit({"phase": "device", "jax": jax.__version__, **device,
          "bytes_limit": hbm(dev)[0],
          "compile_cache_dir": cache_dir,
          "compile_cache_dir_from": origin})
    return device


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------

def _max_err(got, ref, atol=KERNEL_ATOL):
    import numpy as np
    got = np.asarray(got, np.float32)
    ref = np.asarray(ref, np.float32)
    check(np.isfinite(got).all(), "kernel output is not finite")
    err = np.abs(got - ref)
    ok = bool((err <= atol + KERNEL_RTOL * np.abs(ref)).all())
    return float(err.max()), ok


def phase_kernels(seed: int, heads: int = 16, d: int = 128,
                  seq: int = SEQ) -> None:
    import importlib
    import jax
    import jax.numpy as jnp
    import numpy as np
    from paddle_tpu.nn import functional as F
    from paddle_tpu.ops.flash_attention import flash_attention
    from paddle_tpu.ops.paged_attention import (
        QuantizedKV, paged_attention_kernel, quantize_kv,
        ragged_paged_attention)

    fa_mod = importlib.import_module("paddle_tpu.ops.flash_attention")
    check(fa_mod.INTERPRET is False,
          "the Pallas interpret switch is on: kernels would not compile")
    t0 = time.time()
    keys = jax.random.split(jax.random.PRNGKey(seed), 8)

    # flash attention, forward and backward, against the XLA branch of
    # scaled_dot_product_attention
    shape = (2, seq, heads, d)
    q, k, v = (jax.random.normal(kk, shape, jnp.bfloat16)
               for kk in keys[:3])

    def loss(fn):
        return lambda q, k, v: (fn(q, k, v).astype(jnp.float32) ** 2).sum()

    def flash(q, k, v):
        return flash_attention(q, k, v, causal=True, interpret=False)

    def xla(q, k, v):
        return F.scaled_dot_product_attention(
            q, k, v, is_causal=True, training=False, use_flash=False)

    out_f = jax.jit(flash)(q, k, v)
    out_x = jax.jit(xla)(q, k, v)
    g_f = jax.jit(jax.grad(loss(flash), argnums=(0, 1, 2)))(q, k, v)
    g_x = jax.jit(jax.grad(loss(xla), argnums=(0, 1, 2)))(q, k, v)
    errs = {"fwd": _max_err(out_f, out_x)}
    for name, a, b in zip(("dq", "dk", "dv"), g_f, g_x):
        # gradients of a sum of squares over `seq` keys: compare at their
        # own scale
        s = float(jnp.abs(b.astype(jnp.float32)).max()) or 1.0
        errs[name] = _max_err(a.astype(jnp.float32) / s,
                              b.astype(jnp.float32) / s, GRAD_ATOL)
    emit({"phase": "kernels", "kernel": "flash_attention",
          "shape": list(shape), "dtype": "bfloat16", "interpret": False,
          "max_abs_err": {n: round(e, 5) for n, (e, _) in errs.items()},
          "atol": KERNEL_ATOL, "grad_atol_at_unit_scale": GRAD_ATOL,
          "rtol": KERNEL_RTOL})
    check(all(ok for _, ok in errs.values()),
          f"flash attention disagrees with the XLA reference: {errs}")

    # ragged paged attention: rows with full, partial-page, one-token and
    # empty contexts over a shuffled page pool
    # over a STACKED pool, as the engine holds it: the call attends one
    # layer of three and must not see the other two
    rng = np.random.RandomState(seed)
    pages_per_seq = seq // PAGE
    rows = 24
    layers, layer = 3, 1
    num_pages = rows * pages_per_seq // 4 + 1
    lens = rng.randint(1, seq + 1, rows)
    lens[:4] = (seq, 1, PAGE + 3, 0)
    tables = rng.randint(1, num_pages, (rows, pages_per_seq))
    for kv_heads in (heads, heads // 4):
        pool_shape = (layers, num_pages, PAGE, kv_heads, d)
        kf = jax.random.normal(keys[3], pool_shape, jnp.float32)
        vf = jax.random.normal(keys[4], pool_shape, jnp.float32)
        qq = jax.random.normal(keys[5], (rows, heads, d), jnp.bfloat16)
        for pool in ("bf16", "int8"):
            if pool == "int8":
                kq, ks = quantize_kv(kf)
                vq, vs = quantize_kv(vf)
                kk, vv = QuantizedKV(kq, ks), QuantizedKV(vq, vs)
            else:
                kq, vq, ks, vs = (kf.astype(jnp.bfloat16),
                                  vf.astype(jnp.bfloat16), None, None)
                kk, vv = kq, vq
            tb, ln = jnp.asarray(tables, jnp.int32), jnp.asarray(
                lens, jnp.int32)
            got = jax.jit(lambda q, k, v, ks, vs: paged_attention_kernel(
                q, k, v, tb, ln, layer=layer, interpret=False,
                k_scales=ks, v_scales=vs))(qq, kq, vq, ks, vs)
            ref = jax.jit(lambda q, k, v: ragged_paged_attention(
                q, k, v, tb, ln, impl="xla", layer=layer))(qq, kk, vv)
            err, ok = _max_err(got, ref)
            emit({"phase": "kernels", "kernel": "paged_attention",
                  "pool": pool, "heads": heads, "kv_heads": kv_heads,
                  "head_dim": d, "page_size": PAGE, "rows": rows,
                  "layers": layers, "layer": layer,
                  "max_len": seq, "interpret": False,
                  "max_abs_err": round(err, 5), "atol": KERNEL_ATOL,
                  "rtol": KERNEL_RTOL})
            check(ok, f"paged attention ({pool}, kv_heads {kv_heads}) "
                      f"disagrees with _gathered_attention: {err}")
            check(float(jnp.abs(got[3].astype(jnp.float32)).max()) == 0.0,
                  "an empty context must give a zero row")
    time_paged_attention(seed, heads, d, seq)
    time_ssd_step(seed)
    time_ssd_chunk(seed)
    time_kda_chunk(seed)
    time_gdn_chunk(seed)
    time_grouped_matmul(seed)
    emit({"phase": "kernels", "seconds": round(time.time() - t0, 1)})


def time_paged_attention(seed: int, heads: int = 16, d: int = 128,
                         seq: int = SEQ, layers: int = 4,
                         num_pages: int = 2721, calls: int = 24) -> None:
    """The kernel's time a call beside the gathered path's, at the shapes
    the serving benchmark's engine gives them: a bf16 pool of 2,721 pages
    a layer under a 2048-token table; a decode tick's 32 rows (contexts
    of 16 to 896 tokens, lognormal around 200) and a mixed tick's 96 (the
    same 32 after 64 chunk rows of two prompts, each row its sequence's
    table and a limit one longer than the row before). Then the window /
    full attention cell's two decode calls (:func:`swa_decode_calls`: 6
    and 9 query heads a K/V head, the fold on the MXU). A call's time is
    that of ``calls`` chained calls in one program, over their number.
    Smoke readings of one layer's call, not a benchmark."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    rng = np.random.RandomState(seed + 7)
    pages_per_seq = seq // PAGE
    keys = jax.random.split(jax.random.PRNGKey(seed + 7), 3)
    pool_shape = (layers, num_pages, PAGE, heads, d)
    k_pool = jax.random.normal(keys[0], pool_shape, jnp.bfloat16)
    v_pool = jax.random.normal(keys[1], pool_shape, jnp.bfloat16)
    decode_lens = np.clip(rng.lognormal(np.log(200.0), 0.7, 32), 16,
                          896).astype(np.int32)
    decode_lens[5] = 0                          # an empty slot
    decode_tables = np.zeros((32, pages_per_seq), np.int32)
    free = rng.permutation(np.arange(1, num_pages))
    for r, n in enumerate(decode_lens):
        need = -(-int(n) // PAGE)
        decode_tables[r, :need], free = free[:need], free[need:]
    # two prompts mid-prefill: 40 rows from position 130, 24 from 0
    chunk_lens = np.concatenate([130 + 1 + np.arange(40),
                                 1 + np.arange(24)]).astype(np.int32)
    chunk_tables = np.zeros((64, pages_per_seq), np.int32)
    for rows_, upto in ((slice(0, 40), 170), (slice(40, 64), 24)):
        need = -(-upto // PAGE)
        chunk_tables[rows_, :need], free = free[:need], free[need:]
    shapes = {
        "decode": (decode_tables, decode_lens),
        "mixed": (np.concatenate([chunk_tables, decode_tables]),
                  np.concatenate([chunk_lens, decode_lens]))}
    for name, (tables, lens) in shapes.items():
        qq = jax.random.normal(keys[2], (len(lens), heads, d), jnp.bfloat16)
        time_attention_call(name, qq, k_pool, v_pool, tables, lens, None,
                            calls)
    del k_pool, v_pool
    for name, call in swa_decode_calls(seed):
        # the gathered path moves the whole 9,216-token table a row and
        # repeats it a query head: agreement four rows at a time, no time
        time_attention_call(name, *call, calls, gather_rows=4)


def swa_decode_calls(seed: int, rows: int = 32, kv_heads: int = 8,
                     d: int = 128, pages_per_seq: int = 576,
                     window: int = 512):
    """The row walk's two calls in a decode tick of ``agent_closed_swa``
    (Laguna-S-2.1's widths: 8 K/V heads of 128, pages of 16 tokens, bf16,
    tables of 576 pages), 32 rows with contexts lognormal around 2,300
    (512 to 9,216): ``swa_full`` (F) 48 query heads over a full-attention
    group's pool (3 layers of 18,433 pages), ``swa_window`` (W) 72 heads
    with ``starts = limit - 512`` over a window group's (9 layers of 1,569
    pages: a row holds the 33 pages of its window). Yields ``(name, (q,
    k_pool, v_pool, tables, lens, starts))``, a call's pools made when it
    is asked for."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    rng = np.random.RandomState(seed + 41)
    lens = np.clip(rng.lognormal(np.log(2300.0), 0.55, rows), 512,
                   pages_per_seq * PAGE).astype(np.int32)
    lens[0], lens[1] = pages_per_seq * PAGE, 512      # both ends of the range
    keys = jax.random.split(jax.random.PRNGKey(seed + 41), 3)

    for name, (heads, layers, num_pages, bound) in {
            "swa_full": (48, 3, 18433, False),
            "swa_window": (72, 9, 1569, True)}.items():
        starts = np.maximum(lens - window, 0) if bound else None
        tables = np.zeros((rows, pages_per_seq), np.int32)
        free = rng.permutation(np.arange(1, num_pages))
        for r, n in enumerate(lens):
            first = int(starts[r]) // PAGE if bound else 0
            need = -(-int(n) // PAGE) - first
            tables[r, first:first + need] = free[:need]
            free = free[need:]
        shape = (layers, num_pages, PAGE, kv_heads, d)
        yield name, (
            jax.random.normal(keys[2], (rows, heads, d), jnp.bfloat16),
            jax.random.normal(keys[0], shape, jnp.bfloat16),
            jax.random.normal(keys[1], shape, jnp.bfloat16),
            tables, lens, starts)


def time_attention_call(name, qq, k_pool, v_pool, tables, lens, starts,
                        calls: int, gather_rows=None) -> None:
    """One line of ``time_paged_attention``: the kernel held to the
    gathered path, then ms a call of ``calls`` chained calls in one program
    (the layer of the stacked pool going round), the live pages a call
    reads and the GB/s that makes of them (K and V, as stored).
    ``gather_rows``: where the gathered path cannot hold all the rows at
    once it is asked for agreement that many rows at a time, and not
    timed."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from paddle_tpu.ops.paged_attention import ragged_paged_attention

    rows, layers = len(lens), k_pool.shape[0]
    tb, ln = jnp.asarray(tables), jnp.asarray(lens)
    st = None if starts is None else jnp.asarray(starts)
    ms, outs = {}, {}
    for impl in ("pallas", "xla"):
        one = jax.jit(lambda q, k, v, tb, ln, st, impl=impl:
                      ragged_paged_attention(q, k, v, tb, ln, impl=impl,
                                             layer=layers // 2, starts=st))
        step = gather_rows if impl == "xla" and gather_rows else rows
        outs[impl] = jnp.concatenate([
            one(qq[i:i + step], k_pool, v_pool, tb[i:i + step],
                ln[i:i + step], None if st is None else st[i:i + step])
            for i in range(0, rows, step)]).block_until_ready()
        if step < rows:
            continue

        # a tick's worth of calls in ONE program, each fed the one
        # before: a dispatch from Python costs more than the kernel runs
        def tick(q, k, v, impl=impl):
            return jax.lax.fori_loop(
                0, calls, lambda i, q: ragged_paged_attention(
                    q, k, v, tb, ln, impl=impl, layer=i % layers,
                    starts=st), q)

        fn = jax.jit(tick)
        fn(qq, k_pool, v_pool).block_until_ready()
        t1 = time.perf_counter()
        for _ in range(3):
            out = fn(qq, k_pool, v_pool)
        out.block_until_ready()
        ms[impl] = (time.perf_counter() - t1) * 1e3 / (3 * calls)
    err, ok = _max_err(outs["pallas"], outs["xla"])
    first = np.zeros_like(lens) if starts is None else np.asarray(starts)
    live = int(sum(-(-int(n) // PAGE) - int(lo) // PAGE
                   for n, lo in zip(lens, first)))
    page_bytes = 2 * k_pool[0, 0].nbytes
    line = {"phase": "kernels", "kernel": "paged_attention",
            "timed": name, "rows": rows, "heads": qq.shape[1],
            "kv_heads": k_pool.shape[3], "live_pages_read": live,
            "table_pages": rows * tables.shape[1],
            "kernel_ms_per_call": round(ms["pallas"], 4),
            "kernel_gb_per_s_of_live_pages": round(
                live * page_bytes / ms["pallas"] / 1e6, 1),
            "max_abs_err": round(err, 5), "calls": calls}
    if "xla" in ms:
        line["gathered_ms_per_call"] = round(ms["xla"], 4)
    emit(line)
    check(ok, f"paged attention at the {name} tick's shapes disagrees "
              f"with _gathered_attention: {err}")


def time_ssd_step(seed: int, slots: int = 64, heads: int = 128,
                  d_head: int = 64, d_state: int = 128, layers: int = 3,
                  calls: int = 9, head_blocks=(None,)) -> None:
    """The state step's kernel beside ``ssd_step`` as ``ragged_forward``
    calls it off the TPU (slice the slots' rows, step, write them back),
    at the hybrid serving benchmark's shapes: 65 float32 state rows of
    128 x 64 x 128 a layer, 64 decode rows of bf16 activations, all of
    them live and with 40 of 64 live. A call's time is that of ``calls``
    chained calls in one program that donates the state, over their
    number; GB/s counts a LIVE row's state once read and once written,
    so ``ssd_step``, which moves every row, reads lower on a part-full
    batch. Both paths are checked against each other first. Smoke
    readings of one layer's call, not a benchmark."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from paddle_tpu.ops.ssd import ssd_step, ssd_step_kernel

    rng = np.random.RandomState(seed + 11)
    keys = jax.random.split(jax.random.PRNGKey(seed + 11), 8)
    r = slots
    x = jax.random.normal(keys[0], (r, heads, d_head), jnp.bfloat16)
    dt = jax.nn.softplus(jax.random.normal(keys[1], (r, heads)))
    a = -jnp.exp(jax.random.normal(keys[2], (heads,)))
    d = jax.random.normal(keys[3], (heads,))
    b = jax.random.normal(keys[4], (r, d_state))
    c = jax.random.normal(keys[5], (r, d_state))
    row_bytes = heads * d_head * d_state * 4

    def fresh_state():
        return tuple(
            jax.random.normal(k, (slots + 1, heads, d_head, d_state),
                              jnp.float32)
            for k in jax.random.split(keys[6], layers))

    def plain(s, x, live, first):
        old = s[:r]
        y, new = ssd_step(
            x, jnp.where(live[:, None], dt, 0.0), a, b, c, d,
            jnp.where((first & live)[:, None, None, None], 0.0, old))
        return y, s.at[:r].set(new)

    def kernel(hb):
        return lambda s, x, live, first: ssd_step_kernel(
            x, dt, a, b, c, d, s, live, first, head_block=hb,
            interpret=False)

    def chained(step):
        # the calls of a tick in ONE program: a layer's state goes from
        # call to call, the outputs are summed (fed back as the next
        # call's input they would grow without bound on a row whose
        # dt B.C is large)
        def run(state, x, live, first):
            state = list(state)
            total = jnp.zeros(x.shape, jnp.float32)
            for i in range(calls):
                y, state[i % layers] = step(state[i % layers], x, live,
                                            first)
                total = total + y
            return total, tuple(state)
        return jax.jit(run, donate_argnums=(0,))

    part = np.ones((r,), bool)
    part[rng.permutation(r)[:r - 40]] = False
    first = np.zeros((r,), bool)
    first[[3, 17]] = True
    for live in (np.ones((r,), bool), part):
        name = f"{int(live.sum())}_of_{r}_live"
        lv, fs = jnp.asarray(live), jnp.asarray(first)
        ref_x, ref_state = chained(plain)(fresh_state(), x, lv, fs)
        steps = {"ssd_step": plain}
        steps.update({f"kernel_hb{hb or 'auto'}": kernel(hb)
                      for hb in head_blocks})
        for what, step in steps.items():
            fn = chained(step)
            state = fresh_state()
            got_x, state = fn(state, x, lv, fs)
            if step is not plain:
                # a row that is not live: the skip term from the kernel,
                # whatever the plain step makes of a state it leaves alone
                err, ok = _max_err(got_x[lv], ref_x[lv])
                # float32 beside float32: a few units in the last place
                # of the largest value a state holds
                s_err = max(float(jnp.abs(g - w).max())
                            for g, w in zip(state, ref_state))
                s_max = max(float(jnp.abs(w).max()) for w in ref_state)
                check(ok and s_err <= 1e-5 * max(s_max, 1.0),
                      f"{what} ({name}) disagrees with ssd_step: y {err}, "
                      f"state {s_err} of {s_max}")
            else:
                err = s_err = 0.0
            jax.block_until_ready(state)
            t1 = time.perf_counter()
            for _ in range(3):
                got_x, state = fn(state, x, lv, fs)
            jax.block_until_ready((got_x, state))
            ms = (time.perf_counter() - t1) * 1e3 / (3 * calls)
            del state
            n_live = int(live.sum())
            emit({"phase": "kernels", "kernel": "ssd_step", "timed": name,
                  "path": what, "rows": r, "live_rows": n_live,
                  "state_row_bytes": row_bytes,
                  "ms_per_call": round(ms, 4),
                  "live_state_gb_per_s": round(
                      2 * n_live * row_bytes / ms / 1e6, 1),
                  "max_abs_err_y": round(err, 6),
                  "max_abs_err_state": round(s_err, 8), "calls": calls})
        del ref_state
        free_device_memory()


def time_ssd_chunk(seed: int, rows: int = 256, slots: int = 64,
                   heads: int = 128, d_head: int = 64, d_state: int = 128,
                   max_seqs: int = 8, layers: int = 3, calls: int = 9,
                   head_blocks=(None,)) -> None:
    """The chunk scan's kernel beside ``ssd_chunked`` as ``ragged_forward``
    calls it off the TPU (gather the chunk's state rows, zero the fresh
    ones, scan, scatter them back), at the hybrid serving benchmark's
    shapes: a chunk of 256 rows of bf16 activations, 65 float32 state rows
    of 128 x 64 x 128 a layer, up to 8 sequences a chunk; with 1, 2 and 8
    sequences in it (the last with padded rows, the second with a fresh
    sequence). A call's time is that of ``calls`` chained calls in one
    program that donates the state, over their number. Whole state arrays
    are compared: a row without a sequence in the chunk must hold. Smoke
    readings of one layer's call, not a benchmark."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from paddle_tpu.ops.ssd import ssd_chunk_gathered, ssd_chunk_kernel

    rng = np.random.RandomState(seed + 13)
    keys = jax.random.split(jax.random.PRNGKey(seed + 13), 8)
    t = rows
    x = jax.random.normal(keys[0], (t, heads, d_head), jnp.bfloat16)
    # the model's own ranges: dt in [1e-3, 1e-1], A in -[1, 16]
    dt = jnp.exp(jax.random.uniform(keys[1], (t, heads), jnp.float32,
                                    np.log(1e-3), np.log(1e-1)))
    a = -jax.random.uniform(keys[2], (heads,), jnp.float32, 1.0, 16.0)
    d = jax.random.normal(keys[3], (heads,))
    b = jax.random.normal(keys[4], (t, d_state), jnp.bfloat16)
    c = jax.random.normal(keys[5], (t, d_state), jnp.bfloat16)
    row_bytes = heads * d_head * d_state * 4

    def fresh_state():
        return tuple(
            jax.random.normal(k, (slots + 1, heads, d_head, d_state),
                              jnp.float32)
            for k in jax.random.split(keys[6], layers))

    def plain(s, x, seg, seg_rows, fresh):
        return ssd_chunk_gathered(x, dt, a, b, c, d, s, seg, seg_rows,
                                  fresh, chunk=rows)

    def kernel(hb):
        return lambda s, x, seg, seg_rows, fresh: ssd_chunk_kernel(
            x, dt, a, b, c, d, s, seg, seg_rows, fresh, chunk=rows,
            head_block=hb, interpret=False)

    def chained(step):
        def run(state, x, seg, seg_rows, fresh):
            state = list(state)
            total = jnp.zeros(x.shape, jnp.float32)
            for i in range(calls):
                y, state[i % layers] = step(state[i % layers], x, seg,
                                            seg_rows, fresh)
                total = total + y
            return total, tuple(state)
        return jax.jit(run, donate_argnums=(0,))

    # (lengths of the sequences in the chunk, which of them start here)
    cases = {"1_seq": ((t,), ()), "2_seqs": ((t - 96, 96), (1,)),
             "8_seqs": ((57, 9, 40, 1, 64, 23, 31, 17), (2, 5))}
    for name, (lens, starts) in cases.items():
        seg = np.full((t,), max_seqs, np.int32)
        seg[:sum(lens)] = np.repeat(np.arange(len(lens)), lens)
        seg_rows = np.full((max_seqs,), slots, np.int32)
        seg_rows[:len(lens)] = rng.permutation(slots)[:len(lens)]
        fresh = np.zeros((max_seqs,), bool)
        fresh[list(starts)] = True
        valid = jnp.asarray(seg < max_seqs)
        args = (jnp.asarray(seg), jnp.asarray(seg_rows), jnp.asarray(fresh))
        ref_y, ref_state = chained(plain)(fresh_state(), x, *args)
        steps = {"ssd_chunked": plain}
        steps.update({f"kernel_hb{hb or 'auto'}": kernel(hb)
                      for hb in head_blocks})
        for what, step in steps.items():
            fn = chained(step)
            got_y, state = fn(fresh_state(), x, *args)
            if step is not plain:
                check(bool(jnp.isfinite(got_y[valid]).all()),
                      f"{what} ({name}): y is not finite")
                # float32 beside float32, the same sums in another order
                y_err = float(jnp.abs(got_y - ref_y)[valid].max())
                y_max = float(jnp.abs(ref_y[valid]).max())
                s_err = max(float(jnp.abs(g - w).max())
                            for g, w in zip(state, ref_state))
                s_max = max(float(jnp.abs(w).max()) for w in ref_state)
                check(y_err <= 2e-5 * max(y_max, 1.0)
                      and s_err <= 2e-5 * max(s_max, 1.0),
                      f"{what} ({name}) disagrees with ssd_chunked: y "
                      f"{y_err} of {y_max}, state {s_err} of {s_max}")
            else:
                y_err = s_err = 0.0
            jax.block_until_ready(state)
            t1 = time.perf_counter()
            for _ in range(3):
                got_y, state = fn(state, x, *args)
            jax.block_until_ready((got_y, state))
            ms = (time.perf_counter() - t1) * 1e3 / (3 * calls)
            del state
            emit({"phase": "kernels", "kernel": "ssd_chunk", "timed": name,
                  "path": what, "rows": t, "sequences": len(lens),
                  "state_row_bytes": row_bytes,
                  "ms_per_call": round(ms, 4),
                  "max_abs_err_y": round(y_err, 7),
                  "max_abs_err_state": round(s_err, 8), "calls": calls})
        del ref_state
        free_device_memory()


def time_kda_chunk(seed: int, rows: int = 256, slots: int = 48,
                   heads: int = 32, d: int = 128, max_seqs: int = 8,
                   layers: int = 3, calls: int = 20, v_dim: int = None,
                   v_live: int = None, scalar_decay: bool = False,
                   write_max: float = 1.0, kernel: str = "kda_chunk") -> None:
    """The delta rule's chunk form (``ops/kda.py``, plain ``jax.numpy`` on
    every platform) as a mixed tick of ``reason_closed_kda`` calls it a KDA
    layer: 256 packed prompt rows of 32 heads of 128, float32, as the model
    makes them (unit keys, ``q`` scaled, ``log a`` <= 0), over a layer's 48
    + 1 state rows, up to 8 sequences a chunk; with 1, 2 and 8 sequences in
    it (the second with a fresh one, the last with padded rows). One call's
    ``o`` (live rows) and whole state array are held to ``kda_recurrence``,
    a sequence at a time, on the chip (a row without a sequence in the
    chunk must hold); then ms a call of ``calls`` chained calls in one
    program that donates the state (a mixed tick runs twenty such layers),
    ``layers`` state arrays going round, EACH CALL WITH ROWS OF ITS OWN:
    what does not depend on the state, the inverse among it, is else
    computed once for all the calls of the program. Smoke readings of one
    layer's call, not a benchmark. :func:`time_gdn_chunk` runs the same
    with the other model's shapes: a state ``[heads, d, v_dim]`` whose
    last ``v_dim - v_live`` columns are the stored padding (zero values, a
    zero state), ONE decay a head (``scalar_decay``: ``log a`` ``[T, heads,
    1]``, the chunk form's scalar pair products) and write strengths up to
    ``write_max``. Then the step call of a decode tick at the same shapes
    (:func:`time_delta_step`: ``kda_step`` beside the kernel)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from paddle_tpu.ops.kda import (kda_chunk_gathered, kda_recurrence,
                                    l2norm)

    rng = np.random.RandomState(seed + 43)
    ks = jax.random.split(jax.random.PRNGKey(seed + 43), 6)
    t = rows
    v_dim = v_dim or d
    shape = (calls, t, heads, d)
    stored = jnp.arange(v_dim) < (v_live or v_dim)
    inputs = (l2norm(jax.random.normal(ks[0], shape)) * d ** -0.5,
              l2norm(jax.random.normal(ks[1], shape)),
              jax.random.normal(ks[2], shape[:3] + (v_dim,)) * stored,
              -0.3 * jnp.exp(jax.random.normal(
                  ks[3], shape[:3] + (1,) if scalar_decay else shape)),
              write_max * jax.nn.sigmoid(jax.random.normal(ks[4],
                                                           shape[:3])))
    first = tuple(x[0] for x in inputs)

    def fresh_state():
        return tuple(jax.random.normal(k, (slots + 1, heads, d, v_dim),
                                       jnp.float32) * stored
                     for k in jax.random.split(ks[5], layers))

    def run(state, inputs, seg, seg_rows, fresh):
        state = list(state)
        total = jnp.zeros(inputs[2].shape[1:], jnp.float32)
        for i in range(calls):
            o, state[i % layers] = kda_chunk_gathered(
                *(x[i] for x in inputs), state[i % layers], seg, seg_rows,
                fresh)
            total = total + o
        return total, tuple(state)

    one = jax.jit(kda_chunk_gathered)
    by_tokens = jax.jit(kda_recurrence)
    chained = jax.jit(run, donate_argnums=(0,))
    # (lengths of the sequences in the chunk, which of them start here)
    cases = {"1_seq": ((t,), ()), "2_seqs": ((t - 96, 96), (1,)),
             "8_seqs": ((57, 9, 40, 1, 64, 23, 31, 17), (2, 5))}
    for name, (lens, starts) in cases.items():
        seg = np.full((t,), max_seqs, np.int32)
        seg[:sum(lens)] = np.repeat(np.arange(len(lens)), lens)
        seg_rows = np.full((max_seqs,), slots, np.int32)
        seg_rows[:len(lens)] = rng.permutation(slots)[:len(lens)]
        fresh = np.zeros((max_seqs,), bool)
        fresh[list(starts)] = True
        args = (jnp.asarray(seg), jnp.asarray(seg_rows), jnp.asarray(fresh))
        start = fresh_state()[0]
        o, new = one(*first, start, *args)
        want_new, o_err, at = start, 0.0, 0
        for i, n in enumerate(lens):
            row = int(seg_rows[i])
            want_o, want_s = by_tokens(
                *(x[at:at + n] for x in first),
                jnp.zeros_like(start[row]) if fresh[i] else start[row])
            o_err = max(o_err, float(jnp.abs(o[at:at + n] - want_o).max()))
            want_new = want_new.at[row].set(want_s)
            at += n
        # the scratch row takes what the absent entries of seg_rows write
        s_err = float(jnp.abs(new - want_new)[:slots].max())
        check(bool(jnp.isfinite(o[:at]).all()),
              f"kda_chunk_gathered ({name}): o is not finite")
        # float32 beside float32, the same sums in another order
        check(o_err <= 2e-5 and s_err <= 2e-5 * float(jnp.abs(start).max()),
              f"kda_chunk_gathered ({name}) disagrees with kda_recurrence: "
              f"o {o_err}, state {s_err}")
        del start, new, want_new
        total, state = chained(fresh_state(), inputs, *args)
        jax.block_until_ready(state)
        t1 = time.perf_counter()
        for _ in range(3):
            total, state = chained(state, inputs, *args)
        jax.block_until_ready((total, state))
        ms = (time.perf_counter() - t1) * 1e3 / (3 * calls)
        del state
        emit({"phase": "kernels", "kernel": kernel, "timed": name,
              "path": "kda_chunk_gathered", "rows": t,
              "sequences": len(lens), "heads": heads, "head_dim": d,
              "value_dim": v_dim, "scalar_decay": scalar_decay,
              "ms_per_call": round(ms, 4), "max_abs_err_o": round(o_err, 8),
              "max_abs_err_state": round(s_err, 8), "calls": calls})
        free_device_memory()
    del inputs, first
    time_delta_step(seed, kernel.replace("chunk", "step"), slots, heads, d,
                    v_dim, v_live or v_dim, scalar_decay, write_max,
                    layers=layers)


def time_gdn_chunk(seed: int, slots: int = 64, heads: int = 30, dk: int = 96,
                   v_live: int = 192, v_dim: int = 256, layers: int = 3,
                   calls: int = 12) -> None:
    """:func:`time_kda_chunk` with the delta rule with ONE decay a head as
    ``reason_closed_gdn`` calls it a ``linear_attention`` layer: the chunk
    form over 256 packed prompt rows of 30 heads of 96 x 192 (the state
    stored ``[30, 96, 256]``), write strengths in (0, 2), held to
    ``kda_recurrence`` fed the broadcast decay; then the step call over the
    64 slots' rows (:func:`time_delta_step`)."""
    time_kda_chunk(seed, slots=slots, heads=heads, d=dk, layers=layers,
                   calls=calls, v_dim=v_dim, v_live=v_live,
                   scalar_decay=True, write_max=2.0, kernel="gdn_chunk")


def copy_state_tiles(state, live, tile_bytes: int = 1 << 20):
    """The tile walk of ``ops/kda.py kda_step_kernel`` with nothing but the
    copies: every live row's ``[head block, K, V]`` tiles of ``state``
    ``[slots + 1, H, K, V]`` brought into VMEM and sent back as they are, in
    place, a row that is not live naming the tile the pipeline holds
    (``tile_bytes``: the step kernels' budget a tile). What the kernel's
    arithmetic hides behind, timed beside it."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    from paddle_tpu.ops.ssd import held_tiles

    slots, heads, dk, dv = state.shape
    hb = max(1, min(heads, tile_bytes // (dk * dv * 4)))
    nb = -(-heads // hb)
    row, blk = held_tiles(live, slots, nb)

    def index(r, b, row_ref, blk_ref):
        return row_ref[r], jnp.where(blk_ref[r] < 0, b, blk_ref[r]), 0, 0

    def body(row_ref, blk_ref, s_ref, o_ref):
        o_ref[...] = s_ref[...]

    tile = pl.BlockSpec((1, hb, dk, dv), index)
    return pl.pallas_call(
        body,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(live.shape[0], nb),
            in_specs=[tile], out_specs=tile),
        out_shape=jax.ShapeDtypeStruct(state.shape, state.dtype),
        input_output_aliases={2: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        name="copy_state_tiles",
    )(row, blk, state)


def time_delta_step(seed: int, kernel: str, slots: int, heads: int, dk: int,
                    v_dim: int, v_live: int, scalar_decay: bool,
                    write_max: float, layers: int = 3,
                    calls: int = 12) -> None:
    """ONE STEP CALL of the delta rule over a layer's ``slots`` rows, as a
    decode tick calls it a delta-rule layer: ``kda_step`` over the slots'
    rows sliced out and written back (every engine's path today) beside
    ``kda_step_kernel`` over the whole array in place (``state_impl``
    ``"pallas"``, which no model's spec names yet), same inputs. First one
    call of each with rows that are not live and rows that start a
    sequence among them: ``o`` and
    the WHOLE state array held to THE EQUATION written out here (``S' = a S
    + b k (v - k^T a S)^T``, ``o = S'^T q`` at ``highest``; nothing of
    ``ops/kda.py``: ``kda_recurrence`` is ``kda_step`` scanned and could not
    hold it), the rows that are not live and the scratch row bit for bit,
    and ``kda_step`` to the chunk form of one row a sequence. Then ms a
    call of ``calls`` chained calls in one program that donates the state,
    every row live, each call with inputs of its own, and beside them the
    tiles' copies alone (:func:`copy_state_tiles`: the same walk of the
    same tiles with no arithmetic, which is what bounds the kernel). GB/s
    counts the rows' state read once and written once AS STORED. Smoke
    readings of one layer's call, not a benchmark."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from paddle_tpu.ops.kda import (kda_chunked, kda_step, kda_step_kernel,
                                    l2norm)

    ks = jax.random.split(jax.random.PRNGKey(seed + 47), 6)
    stored = jnp.arange(v_dim) < v_live
    shape = (calls, slots, heads, dk)
    inputs = (l2norm(jax.random.normal(ks[0], shape)) * dk ** -0.5,
              l2norm(jax.random.normal(ks[1], shape)),
              jax.random.normal(ks[2], shape[:3] + (v_dim,)) * stored,
              -0.3 * jnp.exp(jax.random.normal(
                  ks[3], shape[:3] + (1,) if scalar_decay else shape)),
              write_max * jax.nn.sigmoid(jax.random.normal(ks[4],
                                                           shape[:3])))
    all_live = jnp.ones((slots,), bool)
    none_first = jnp.zeros((slots,), bool)

    def fresh_state():
        return tuple(jax.random.normal(k, (slots + 1, heads, dk, v_dim),
                                       jnp.float32) * stored
                     for k in jax.random.split(ks[5], layers))

    def plain(s, live, first, *x):
        # as models/kimi_linear.py delta_rule_rows calls it off the TPU
        o, new = kda_step(*x, jnp.where(
            (first & live)[:, None, None, None], 0.0, s[:slots]))
        return o, s.at[:slots].set(new)

    def through_kernel(s, live, first, *x):
        return kda_step_kernel(*x, s, live, first, interpret=False)

    def copies_alone(s, live, first, *x):
        return jnp.zeros_like(x[2]), copy_state_tiles(s, live)

    def definition(q, k, v, log_a, b, s):
        hi = jax.lax.Precision.HIGHEST
        s = jnp.exp(log_a)[..., None] * s
        s = s + (b[..., None] * k)[..., None] * (
            v - jnp.einsum("rhk,rhkv->rhv", k, s, precision=hi)
        )[..., None, :]
        return jnp.einsum("rhk,rhkv->rhv", q, s, precision=hi), s

    # -- one call, held to the equation ------------------------------------
    rng = np.random.RandomState(seed + 47)
    live = np.ones((slots,), bool)
    live[rng.permutation(slots)[:slots // 5]] = False
    first = np.zeros((slots,), bool)
    first[rng.permutation(slots)[:4]] = True
    lv, fs = jnp.asarray(live), jnp.asarray(first)
    start = fresh_state()[0]
    x0 = tuple(x[0] for x in inputs)
    # a dead row as the model's gates make it: no decay, no write
    x_gated = x0[:3] + (jnp.where(lv[:, None, None], x0[3], 0.0),
                        jnp.where(lv[:, None], x0[4], 0.0))
    entering = jnp.where((fs & lv)[:, None, None, None], 0.0, start[:slots])
    def_o, def_new = jax.jit(definition)(*x0, entering)
    want = start.at[:slots].set(
        jnp.where(lv[:, None, None, None], def_new, start[:slots]))
    s_max = float(jnp.abs(start).max())
    errs = {}
    for path, step in (("kda_step", jax.jit(plain)),
                       ("kda_step_kernel", lambda s, lv, fs, *x:
                        kda_step_kernel(*x, s, lv, fs, interpret=False))):
        o, new = step(start, lv, fs, *x_gated)
        o_err = float(jnp.abs(o - def_o)[live].max())
        s_err = float(jnp.abs(new - want).max())
        check(o_err <= 1e-5 and s_err <= 1e-5 * s_max,
              f"{path} ({kernel}) disagrees with the equation: o {o_err}, "
              f"state {s_err} of {s_max}")
        if path == "kda_step_kernel":
            held = np.append(~live, True)           # and the scratch row
            check(bool(jnp.array_equal(new[held], start[held])),
                  f"{path} ({kernel}) moved a row that is not live")
        errs[path] = (o_err, s_err)
    # the same rows as a packed run of one row a sequence
    o, _ = jax.jit(kda_step)(*x0, start[:slots])
    chunk_o, _ = jax.jit(kda_chunked)(
        *x0, start[:slots], jnp.arange(slots, dtype=jnp.int32))
    chunk_err = float(jnp.abs(o - chunk_o).max())
    check(chunk_err <= 2e-5,
          f"kda_step ({kernel}) disagrees with the chunk form: o {chunk_err}")
    del start, new, want, def_new, entering

    # -- ms a call, every row live -----------------------------------------
    def chained(step):
        def run(state, inputs):
            state = list(state)
            total = jnp.zeros(inputs[2].shape[1:], jnp.float32)
            for i in range(calls):
                o, state[i % layers] = step(
                    state[i % layers], all_live, none_first,
                    *(x[i] for x in inputs))
                total = total + o
            return total, tuple(state)
        return jax.jit(run, donate_argnums=(0,))

    moved = 2 * slots * heads * dk * v_dim * 4
    chain = None        # kda_step's sum of outputs and states after a run
    for path, step in (("kda_step", plain),
                       ("kda_step_kernel", through_kernel),
                       ("kda_step_kernel/copy", copies_alone)):
        fn = chained(step)
        total, state = fn(fresh_state(), inputs)
        jax.block_until_ready(state)
        if path == "kda_step":
            chain = (total, tuple(jnp.copy(s) for s in state))
        elif path == "kda_step_kernel":
            # the program a tick runs: several calls, every row live
            run_err = max(float(jnp.abs(a - b).max()) for a, b in
                          zip((total,) + state, (chain[0],) + chain[1]))
            check(run_err <= 1e-4 * s_max,
                  f"{path} ({kernel}): {calls} chained calls end "
                  f"{run_err} from kda_step's")
            chain = None
        t1 = time.perf_counter()
        for _ in range(3):
            total, state = fn(state, inputs)
        jax.block_until_ready((total, state))
        ms = (time.perf_counter() - t1) * 1e3 / (3 * calls)
        del state
        line = {"phase": "kernels", "kernel": kernel, "path": path,
                "rows": slots, "heads": heads, "head_dim": dk,
                "value_dim": v_dim, "scalar_decay": scalar_decay,
                "ms_per_call": round(ms, 4),
                "state_gb_per_s_as_stored": round(moved / ms / 1e6, 1),
                "calls": calls}
        if path in errs:
            line.update(max_abs_err_o_to_equation=round(errs[path][0], 8),
                        max_abs_err_state_to_equation=round(errs[path][1],
                                                            8),
                        max_abs_err_o_to_chunk_form=round(chunk_err, 8))
        emit(line)
        free_device_memory()


def phase_gdn_program(seed: int, ticks: int = 4) -> None:
    """``reason_closed_gdn``'s OWN decode program (the benchmark's
    configuration, weights and engine sizes: 8 layers, the whole
    vocabulary, 64 slots, 14,337 pages) under both values of
    ``state_impl``, fed the same tokens, positions and live rows for a few
    ticks from the same random state: ``sum |S|`` a (layer, row, head)
    after every tick, the kernel's program against ``kda_step``'s. Every
    ``linear_attention`` layer's ``o_proj`` is zeroed, so each layer sees
    the same input whatever the step did before it and a fault shows in
    the layer that has it. Not part of the default run (the weights take
    the chip's memory): ``--phase gdn_program``. What it guards: in PR 48
    the kernel's program wrote the LAST such layer's conv rows in place
    before a rematerialised read of them fed the convolution (the
    compiler's schedule, ``tests/test_chip_compile.py
    reads_after_in_place_writes``), its state left ``kda_step``'s by up to
    78% a head from the first tick, and the cell read ``correct`` false."""
    import os
    import jax
    import jax.numpy as jnp
    import numpy as np
    from benchmark import weights_olmo
    from benchmark.systems import serve_olmo
    from paddle_tpu.inference import llm

    root = os.path.dirname(os.path.abspath(__file__))
    with open(os.path.join(root, "benchmark", "configs",
                           "olmo-hybrid-7b-serve-pp4.json")) as f:
        model = json.load(f)
    params = weights_olmo.make(weights_olmo.dims_of(model), seed + 123,
                               jnp.bfloat16)
    linear = [k for k in params if k.endswith("mixer.o_proj.weight")
              and int(k.split(".")[1]) % 4 != 3]
    for k in linear:
        params[k] = jnp.zeros_like(params[k])
    net = serve_olmo.build_net(model, params)
    del params
    net.eval()
    cfg, slots = net.cfg, 64
    rng = np.random.RandomState(seed + 5)
    lives = [np.isin(np.arange(slots), [36, 44]), np.ones(slots, bool),
             rng.rand(slots) < 0.5, np.arange(slots) < 8][:ticks]
    sched, pos = [], np.zeros((slots,), np.int32)
    for live in lives:
        pos = np.where(live, pos + 1, pos).astype(np.int32)
        sched.append((pos.copy(), np.where(live, pos + 1, 0).astype(np.int32),
                      rng.randint(0, cfg.vocab_size, size=(slots,))
                      .astype(np.int32)))
    real = llm._state_impl
    sums = {}
    try:
        for impl in ("xla", "pallas"):
            llm._state_impl = lambda state, impls=None, impl=impl: impl
            eng = llm.LLMEngine(net, max_seqs=slots, page_size=PAGE,
                                num_pages=14337, max_len=3584,
                                prefill_chunk=256, kv_dtype="bf16",
                                attention_impl="pallas")
            try:
                check(eng.state_impl == impl, f"engine took {eng.state_impl}")
                for g in eng._pool.groups:
                    g.tables[:, :16] = 1 + np.arange(slots * 16).reshape(
                        slots, 16)
                conv = tuple(jnp.zeros_like(a) for a in eng.conv_state)
                stored = jnp.arange(cfg.value_width) \
                    < cfg.linear_value_head_dim
                ssm = tuple(
                    jax.random.normal(k, a.shape, jnp.float32) * 0.1 * stored
                    for k, a in zip(jax.random.split(
                        jax.random.PRNGKey(seed + 11), len(eng.ssm_state)),
                        eng.ssm_state))
                kp, vp, seen = eng.k_pages, eng.v_pages, []
                for p, lens, toks in sched:
                    _, kp, vp, conv, ssm = eng._decode_fn(
                        eng._params, eng._buffers, jnp.asarray(toks),
                        eng._stage_decode(p, lens), kp, vp, eng._key, conv,
                        ssm)
                    seen.append(np.asarray(jnp.stack(
                        [jnp.sum(jnp.abs(s), axis=(2, 3)) for s in ssm])))
                sums[impl] = np.stack(seen)     # [ticks, layers, rows, heads]
            finally:
                eng.close()
            del eng, kp, vp, conv, ssm
            free_device_memory()
    finally:
        llm._state_impl = real
    rel = np.abs(sums["pallas"] - sums["xla"]) \
        / np.maximum(np.abs(sums["xla"]), 1e-3)
    worst = rel.max(axis=(0, 2, 3))
    emit({"phase": "gdn_program", "ticks": len(sched),
          "delta_rule_layers": len(linear),
          "max_rel_err_of_sum_abs_state_by_layer":
              [float(f"{x:.3g}") for x in worst]})
    check(float(worst.max()) <= 1e-5,
          f"the kernel's decode program leaves kda_step's: worst relative "
          f"error of sum |S| a (row, head), by layer {worst.tolist()}")
    del net
    free_device_memory()


def time_grouped_matmul(seed: int, held: int = 36, layers: int = 4) -> None:
    """The routed experts' grouped product (``ops/grouped_matmul.py``)
    beside ``jax.lax.ragged_dot`` at the hybrid serving benchmark's shapes:
    36 held experts of ``[4096, 1536]`` and ``[768, 4096]`` bf16, a decode
    tick's 640 (row, expert) pairs and a mixed tick's 3,200, half of them
    held, unevenly, the even experts alone once more (an expert without
    rows is not read). A call's time is that of ``layers`` calls in one
    program, each on its own weights, over their number; GB/s counts the
    weights of the experts that received a row, once. Smoke readings of one
    layer's call, not a benchmark."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from paddle_tpu.ops.grouped_matmul import grouped_matmul

    rng = np.random.default_rng(seed + 13)
    key = jax.random.PRNGKey(seed + 13)

    def many(product):
        return jax.jit(lambda x, sizes, *ws: [product(x, w, sizes)
                                              for w in ws])

    paths = {"ragged_dot": many(jax.lax.ragged_dot),
             "kernel": many(lambda x, w, sizes: grouped_matmul(
                 x, w, sizes, interpret=False))}
    for k, n in ((4096, 1536), (768, 4096)):
        ws = [jax.random.normal(jax.random.fold_in(key, k + i), (held, k, n),
                                jnp.bfloat16) * 0.02 for i in range(layers)]
        for pairs in (640, 3200):
            x = jax.random.normal(jax.random.fold_in(key, pairs), (pairs, k),
                                  jnp.bfloat16)
            sizes = rng.multinomial(pairs // 2,
                                    rng.dirichlet(np.full(held, 3.0)))
            for name, gs in (("every_expert", sizes),
                             ("even_experts", np.where(
                                 np.arange(held) % 2 == 0, sizes, 0))):
                rows, live = int(gs.sum()), int((gs > 0).sum())
                gs = jnp.asarray(gs, jnp.int32)
                outs, ms = {}, {}
                for what, fn in paths.items():
                    outs[what] = jax.block_until_ready(fn(x, gs, *ws))
                    t1 = time.perf_counter()
                    for _ in range(10):
                        out = fn(x, gs, *ws)
                    jax.block_until_ready(out)
                    ms[what] = (time.perf_counter() - t1) * 1e3 / (
                        10 * layers)
                errs = [_max_err(g[:rows], r[:rows]) for g, r in zip(
                    outs["kernel"], outs["ragged_dot"])]
                err, ok = max(e for e, _ in errs), all(o for _, o in errs)
                emit({"phase": "kernels", "kernel": "grouped_matmul",
                      "timed": name, "weights": [held, k, n], "pairs": pairs,
                      "rows_held": rows, "experts_with_rows": live,
                      "kernel_ms_per_call": round(ms["kernel"], 4),
                      "ragged_dot_ms_per_call": round(ms["ragged_dot"], 4),
                      "kernel_weight_gb_per_s": round(
                          live * k * n * 2 / ms["kernel"] / 1e6, 1),
                      "max_abs_err": round(err, 6), "calls": layers})
                check(ok, f"grouped_matmul ({k} x {n}, {pairs} pairs, "
                          f"{name}) disagrees with ragged_dot: {err}")
        del ws
        free_device_memory()


# ---------------------------------------------------------------------------
# serve
# ---------------------------------------------------------------------------

def time_staging(seed: int, reps: int = 300) -> None:
    """What ``LLMEngine._issue`` stages for ONE decode dispatch, at two
    cells' shapes (``chat_closed``: 32 slots, one block table of 128
    columns; ``mixed_len_closed_sink``: 48 slots, two tables of 608): the
    parent's ``jnp.asarray`` a host array (five and six calls, each table
    copied first as ``PagePool.device_tables`` does) beside the one packed
    vector of ``inference/staging.py``. ``host_ms``: until the calls have
    returned, which is what the ``staged`` mark of ``llm.issue.decode``
    reads; ``ready_ms``: until the arrays are on the device. Medians over
    ``reps`` rounds, the two forms taking turns. The packed vector, cut by
    a jitted ``unpack``, must give back every source bit for bit. Smoke
    readings of a quiet process, not a benchmark."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from paddle_tpu.inference.staging import StagedLayout

    rng = np.random.RandomState(seed + 11)
    for cell, slots, widths in (("chat_closed", 32, (128,)),
                                ("mixed_len_closed_sink", 48, (608, 608))):
        ints = [rng.randint(0, 2048, slots).astype(np.int32)
                for _ in range(2)]
        tables = [rng.randint(0, 29185, (slots, w)).astype(np.int32)
                  for w in widths]
        nonces = rng.randint(-2 ** 31, 2 ** 31 - 1, slots).astype(np.int32)
        temps = rng.uniform(0, 1, slots).astype(np.float32)
        temps[:4] = 0.0, -0.0, 1e-45, 1e-30
        layout = StagedLayout(
            [((slots,), np.int32)] * 2 + [(t.shape, np.int32) for t in tables]
            + [((slots,), np.int32), ((slots,), np.float32)])
        sources = ints + tables + [nonces, temps]
        forms = {
            "separate": lambda: [jnp.asarray(a) for a in ints]
            + [jnp.asarray(t.copy()) for t in tables]
            + [jnp.asarray(nonces), jnp.asarray(temps)],
            "packed": lambda: [layout.stage(*sources)],
            "packed_asarray": lambda: [jnp.asarray(layout.pack(*sources))]}
        got = jax.jit(layout.unpack)(layout.stage(*sources))
        check(all(np.asarray(g).tobytes() == a.tobytes()
                  for g, a in zip(got, sources)),
              f"staging ({cell}): an unpacked field is not its source's bits")
        times = {name: ([], []) for name in forms}
        for rep in range(reps + 20):
            for name, form in forms.items():
                t0 = time.perf_counter()
                out = form()
                t1 = time.perf_counter()
                jax.block_until_ready(out)
                t2 = time.perf_counter()
                if rep >= 20:                       # the first rounds warm up
                    times[name][0].append(t1 - t0)
                    times[name][1].append(t2 - t0)
        line = {"phase": "staging", "time_staging": cell, "slots": slots,
                "table_columns": list(widths), "reps": reps,
                "transfers": {"separate": len(sources), "packed": 1},
                "packed_bytes": 4 * layout.size}
        for name, (host, ready) in times.items():
            line[name] = {"host_ms": round(1e3 * float(np.median(host)), 4),
                          "ready_ms": round(1e3 * float(np.median(ready)), 4)}
        line["separate"]["host_ms_a_transfer"] = round(
            line["separate"]["host_ms"] / len(sources), 4)
        emit(line)


def phase_staging(seed: int) -> None:
    t0 = time.time()
    time_staging(seed)
    emit({"phase": "staging", "seconds": round(time.time() - t0, 1)})


def make_prompts(seed: int, vocab: int, lengths, shared_prefix: int):
    """Random prompts; the first two share a page-aligned prefix."""
    import numpy as np
    rng = np.random.RandomState(seed + 1)
    check(shared_prefix % PAGE == 0, "the shared prefix is page-aligned")
    prefix = rng.randint(0, vocab, shared_prefix).tolist()
    prompts = []
    for i, n in enumerate(lengths):
        body = rng.randint(0, vocab, n).tolist()
        prompts.append(prefix + body[shared_prefix:] if i < 2 else body)
    return prompts


def post_generate(url: str, prompt, new_tokens: int) -> dict:
    body = json.dumps({"prompt_ids": prompt, "max_new_tokens": new_tokens,
                       "temperature": 0.0}).encode()
    req = urllib.request.Request(
        url + "/generate", data=body,
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=900) as resp:
        check(resp.status == 200, f"POST /generate -> {resp.status}")
        return json.loads(resp.read())


def post_all(url: str, prompts, new_tokens: int, what: str,
             first_alone: bool = False) -> list:
    """POST every prompt from a thread of its own (the first one alone and
    first, where it seeds the prefix cache); the replies, in order."""
    outs = [None] * len(prompts)
    errors = []

    def ask(i):
        try:
            outs[i] = post_generate(url, prompts[i], new_tokens)
        except Exception as e:  # noqa: BLE001 — reported below
            errors.append(f"request {i}: {e!r}")

    if first_alone:
        ask(0)
    threads = [threading.Thread(target=ask, args=(i,))
               for i in range(int(first_alone), len(prompts))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    check(not errors, f"[{what}] {errors}")
    return outs


def by_length(prompts):
    """Indices of the prompts, grouped by length. net.generate is an eager
    loop whose every op compiles once per shape (about 100 s a new prompt
    length at 24 layers on the chip): prompts of one length go through it,
    and through the teacher-forced forward below, as one batch."""
    groups = {}
    for i, p in enumerate(prompts):
        groups.setdefault(len(p), []).append(i)
    return list(groups.values())


def check_streams(net, prompts, outs, refs, tie_tol=None) -> list:
    """Hold each greedy stream to net.generate's: identical, or every token
    from the first difference on within ``tie_tol`` (TIE_TOL unless given)
    of the top of the reference's own teacher-forced logits."""
    tie_tol = TIE_TOL if tie_tol is None else tie_tol
    import jax.numpy as jnp
    import numpy as np
    verdicts = [None] * len(prompts)
    for group in by_length(prompts):
        for i in group:
            check(len(outs[i]) == len(refs[i]),
                  f"stream {i} has {len(outs[i])} tokens, reference "
                  f"{len(refs[i])}")
            if outs[i] == refs[i]:
                verdicts[i] = {"identical": True}
        if all(verdicts[i] for i in group):
            continue
        n = len(prompts[group[0]])
        ids = jnp.asarray([list(prompts[i]) + list(outs[i][:-1])
                           for i in group], jnp.int32)
        logits = np.asarray(net(ids)[:, n - 1:], np.float32)
        for row, i in enumerate(group):
            if verdicts[i]:
                continue
            out = outs[i]
            first = next(j for j, (a, b) in enumerate(zip(out, refs[i]))
                         if a != b)
            margins = (logits[row].max(-1)
                       - logits[row][np.arange(len(out)), out])
            verdicts[i] = {
                "identical": False, "first_diff": first,
                "margin_at_first_diff": round(float(margins[first]), 4),
                "max_margin_from_there": round(
                    float(margins[first:].max()), 4),
                "tie_tol": tie_tol}
            check(float(margins[first:].max()) <= tie_tol,
                  f"stream {i} leaves the reference beyond a tie: "
                  f"{verdicts[i]}")
    return verdicts


def serve_once(net, impl: str, prompts, refs, num_pages: int,
               new_tokens: int) -> list:
    from paddle_tpu.inference.llm import LLMEngine, serve_llm
    from paddle_tpu.observability import metrics as obs_metrics
    from paddle_tpu.observability import perf

    def device_errors():
        fam = obs_metrics.default_registry().get("llm_device_errors_total")
        return 0.0 if fam is None else float(fam.value)

    def compile_seconds():
        return perf.instance().breakdown().get("llm", {}).get(
            "phases", {}).get("compile", 0.0)

    err0, comp0, t0 = device_errors(), compile_seconds(), time.time()
    eng = LLMEngine(net, max_seqs=MAX_SEQS, page_size=PAGE,
                    num_pages=num_pages, max_len=SEQ, kv_dtype="bf16",
                    attention_impl=impl, prefill_chunk=PREFILL_CHUNK,
                    decode_ticks_per_dispatch=DECODE_TICKS)
    srv = serve_llm(eng)
    try:
        url = "http://%s:%d" % srv.server_address[:2]
        outs = post_all(url, prompts, new_tokens, impl, first_alone=True)
        wall = time.time() - t0
        streams = [o["output_ids"] for o in outs]
        programs = sorted(
            f"{h.kind}{list(h.sig)}" for h in perf.instance().programs()
            if h.component == "llm")
        health = eng.health
        n_err = device_errors() - err0
        emit({"phase": "serve", "attention_impl": impl, "model": MODEL,
              "weights": "bfloat16", "kv_dtype": eng.kv_dtype,
              "num_pages": num_pages,
              "pool_tokens": (num_pages - 1) * PAGE,
              "prefill_chunk": PREFILL_CHUNK,
              "decode_ticks_per_dispatch": DECODE_TICKS,
              "requests": len(prompts),
              "prompt_tokens": [len(p) for p in prompts],
              "tokens_generated": sum(len(s) for s in streams),
              "truncated": sum(bool(o["truncated"]) for o in outs),
              "wall_seconds_with_compile": round(wall, 1),
              "first_dispatch_seconds_per_program_sum": round(
                  compile_seconds() - comp0, 1),
              "program_count": len(programs), "programs": programs,
              "prefix_cache_hit_tokens": eng.n_cached_tokens,
              "host_dispatches": eng.n_host_dispatches,
              "engine_health": health,
              "llm_device_errors_total": n_err})
        check(health == "healthy" and n_err == 0,
              f"[{impl}] the engine caught a device or compile error "
              f"(health {health}, llm_device_errors_total +{n_err})")
        check(not any(o["truncated"] for o in outs),
              f"[{impl}] a request was truncated")
        check(eng.n_cached_tokens >= PAGE,
              f"[{impl}] the shared prefix was not served from the cache")
    finally:
        srv.shutdown()
        srv.server_close()
        eng.close()
    verdicts = check_streams(net, prompts, streams, refs)
    emit({"phase": "serve", "attention_impl": impl,
          "vs_net_generate": verdicts,
          "identical": sum(v["identical"] for v in verdicts)})
    return streams


def phase_serve(seed: int, cfg_overrides=None,
                lengths=(640, 640, 1500, 640, 1500),
                shared_prefix: int = 512,
                new_tokens: int = NEW_TOKENS) -> None:
    import jax
    import jax.numpy as jnp
    import paddle_tpu as pt
    from paddle_tpu.models.gpt import GPTForCausalLM, gpt_config

    cfg = gpt_config(MODEL, hidden_dropout=0.0, attention_dropout=0.0,
                     **(cfg_overrides or {}))
    t0 = time.time()
    pt.seed(seed)
    net = GPTForCausalLM(cfg).astype("bfloat16")
    net.eval()
    n_params = sum(int(v.size) for v in net.state_dict().values())
    prompts = make_prompts(seed, cfg.vocab_size, lengths, shared_prefix)
    build_s = time.time() - t0

    # the reference streams first, while the device holds only the weights
    t0 = time.time()
    refs = [None] * len(prompts)
    for group in by_length(prompts):
        toks = net.generate(
            jnp.asarray([prompts[i] for i in group], jnp.int32),
            max_new_tokens=new_tokens)
        for row, i in enumerate(group):
            refs[i] = [int(t) for t in toks[row, len(prompts[i]):]]
    ref_s = time.time() - t0

    # pool: most of what the weights leave, after the temporaries of the
    # widest program (the xla-impl mixed tick: see PREFILL_CHUNK)
    limit, in_use = hbm(jax.devices()[0])
    rows = PREFILL_CHUNK + MAX_SEQS
    row_bytes = SEQ * cfg.num_kv_heads * cfg.head_dim * 4 * 2
    temp = int(rows * row_bytes * 1.25)
    page_bytes = (cfg.num_layers * PAGE * cfg.num_kv_heads * cfg.head_dim
                  * 2 * 2)
    num_pages = int((limit * HBM_SHARE - in_use - temp) // page_bytes)
    check(num_pages * PAGE >= sum(lengths) + len(lengths) * new_tokens,
          f"pool of {num_pages} pages cannot hold the smoke's requests")
    emit({"phase": "serve", "model": MODEL, "layers": cfg.num_layers,
          "hidden": cfg.hidden_size, "heads": cfg.num_heads,
          "head_dim": cfg.head_dim, "vocab": cfg.vocab_size,
          "params": n_params, "build_seconds": round(build_s, 1),
          "net_generate_seconds": round(ref_s, 1),
          "bytes_limit": limit, "weights_bytes_in_use": in_use,
          "planned_temp_bytes": temp, "kv_pool_bytes": num_pages
          * page_bytes})

    xla = serve_once(net, "xla", prompts, refs, num_pages, new_tokens)
    gc.collect()    # the first engine's pool; the eager ops stay compiled
    pallas = serve_once(net, "pallas", prompts, refs, num_pages, new_tokens)
    emit({"phase": "serve", "xla_vs_pallas_identical_streams": sum(
        a == b for a, b in zip(xla, pallas)), "of": len(prompts)})
    del net
    free_device_memory()



def phase_serve_hybrid(seed: int, layers=("mamba", "mamba", "attention",
                                          "mamba"),
                       lengths=(96, 300, 96, 300), new_tokens: int = 32,
                       experts_held=(0, 8)) -> None:
    """The hybrid decoder (models/granite_hybrid.py) at granite-4.0-h-small's
    published widths and a small depth: Mamba-2 state beside K/V pages,
    dropless top-10-of-72 routing over 8 held experts, served over HTTP by
    ``LLMEngine`` + ``serve_llm`` through both ``attention_impl`` values on
    the default path (mixed ticks), each stream held to ``net.generate``
    (the whole-sequence forward): identical, or a tie within TIE_TOL / 16
    (this model divides its logits by 16)."""
    import jax.numpy as jnp
    import paddle_tpu as pt
    from paddle_tpu.inference.llm import LLMEngine, serve_llm
    from paddle_tpu.models.granite_hybrid import (GraniteHybridConfig,
                                                  GraniteHybridForCausalLM)

    cfg = GraniteHybridConfig(layer_types=layers, vocab_size=50176,
                              experts_held=experts_held,
                              max_position_embeddings=SEQ)
    t0 = time.time()
    pt.seed(seed)
    net = GraniteHybridForCausalLM(cfg).astype("bfloat16")
    net.eval()
    n_params = sum(int(v.size) for v in net.state_dict().values())
    prompts = make_prompts(seed, cfg.vocab_size, lengths, 0)
    refs = [None] * len(prompts)
    for group in by_length(prompts):
        toks = net.generate(
            jnp.asarray([prompts[i] for i in group], jnp.int32),
            max_new_tokens=new_tokens)
        for row, i in enumerate(group):
            refs[i] = [int(t) for t in toks[row, len(prompts[i]):]]
    emit({"phase": "serve_hybrid", "layers": list(layers),
          "hidden": cfg.hidden_size, "experts_held": list(experts_held),
          "of_experts": cfg.num_local_experts, "vocab": cfg.vocab_size,
          "params": n_params,
          "build_and_generate_seconds": round(time.time() - t0, 1)})
    streams = {}
    for impl in ("xla", "pallas"):
        t0 = time.time()
        eng = LLMEngine(net, max_seqs=4, page_size=PAGE, num_pages=256,
                        max_len=SEQ, kv_dtype="bf16", attention_impl=impl,
                        prefill_chunk=cfg.mamba_chunk_size)
        srv = serve_llm(eng)
        try:
            url = "http://%s:%d" % srv.server_address[:2]
            outs = post_all(url, prompts, new_tokens, f"hybrid {impl}")
            check(eng.health == "healthy",
                  f"[hybrid {impl}] engine health {eng.health}")
            check("m" in eng.tick_history,
                  f"[hybrid {impl}] no mixed tick was dispatched")
            held_share = eng.n_moe_pairs_held / max(1, eng.n_moe_pairs)
        finally:
            srv.shutdown()
            srv.server_close()
            eng.close()
        streams[impl] = [o["output_ids"] for o in outs]
        verdicts = check_streams(net, prompts, streams[impl], refs,
                                 TIE_TOL / cfg.logits_scaling)
        emit({"phase": "serve_hybrid", "attention_impl": impl,
              "wall_seconds_with_compile": round(time.time() - t0, 1),
              "moe_held_pair_share": round(held_share, 4),
              "vs_net_generate": verdicts,
              "identical": sum(v["identical"] for v in verdicts)})
        gc.collect()
    emit({"phase": "serve_hybrid", "xla_vs_pallas_identical_streams": sum(
        a == b for a, b in zip(streams["xla"], streams["pallas"])),
        "of": len(prompts)})
    del net
    free_device_memory()


def phase_serve_looped(seed: int, lengths=(150, 70, 33),
                       new_tokens: int = 12) -> None:
    """The looped decoder (models/ouro.py) as the benchmark's configuration
    builds it (``benchmark/configs/ouro-2.6b-serve-bf16.json``: every
    published size, bf16, the pool sized from what the weights leave),
    served over HTTP by ``LLMEngine`` + ``serve_llm`` on the default path: a
    prompt that crosses the 128-row chunk and two that share one, then a
    few decode ticks. Every served token is held to the plain float32
    reference (``benchmark/reference/ouro_looped.py``, a layer's weights at
    a time) by the cell's own measure and limit."""
    import os
    import jax
    import jax.numpy as jnp
    import numpy as np
    from benchmark import weights_looped
    from benchmark.reference import ouro_looped
    from benchmark.systems import serve_looped
    from paddle_tpu.inference.llm import LLMEngine, serve_llm

    root = os.path.dirname(os.path.abspath(__file__))

    def load(*parts):
        with open(os.path.join(root, "benchmark", *parts)) as f:
            return json.load(f)

    cfg = load("configs", "ouro-2.6b-serve-bf16.json")
    limit_gap = load("checks", "reason_closed_looped.json")["worst_gap_limit"]
    d = weights_looped.dims_of(cfg)
    t0 = time.time()
    params = weights_looped.make(d, seed, jnp.bfloat16)
    jax.block_until_ready(params)
    net = serve_looped.build_net(cfg, params)
    net.eval()
    limit, in_use = hbm(jax.devices()[0])
    plan = serve_looped.plan_pages(d, cfg["engine"], cfg["pool"], limit,
                                   in_use)
    emit({"phase": "serve_looped", "layers": d["L"], "passes": d["steps"],
          "hidden": d["H"], "vocab": d["V"],
          "params": weights_looped.n_params(d),
          "build_seconds": round(time.time() - t0, 1),
          "bytes_limit": limit, "weights_bytes_in_use": in_use, **plan})
    prompts = make_prompts(seed, d["V"], lengths, 0)
    t0 = time.time()
    eng = LLMEngine(net, num_pages=plan["num_pages"], **cfg["engine"])
    srv = serve_llm(eng)
    try:
        url = "http://%s:%d" % srv.server_address[:2]
        outs = post_all(url, prompts, new_tokens, "looped")
        check(eng.health == "healthy", f"[looped] engine health {eng.health}")
        check("m" in eng.tick_history and "d" in eng.tick_history,
              "[looped] no mixed or no decode tick was dispatched")
        exits = eng.loop_exit_step_rows.tolist()
        check(exits == [0] * (d["steps"] - 1) + [eng.n_tokens],
              f"[looped] exit steps {exits} of {eng.n_tokens} tokens")
        impl, page_bytes = eng.attention_impl, eng._page_bytes
    finally:
        srv.shutdown()
        srv.server_close()
        eng.close()
    wall = time.time() - t0
    check(not any(o["truncated"] for o in outs), "[looped] truncated")
    del eng, srv, net
    gc.collect()
    t0 = time.time()
    pad = max(lengths) + new_tokens
    ids = np.zeros((len(prompts), pad), np.int32)
    served = np.zeros_like(ids)
    for b, (p, o) in enumerate(zip(prompts, outs)):
        seq = list(p) + list(o["output_ids"])
        ids[b, :len(seq)] = seq
        served[b, len(p) - 1:len(seq) - 1] = o["output_ids"]
    got = jax.device_get(ouro_looped.served_gaps(
        params, ids, np.asarray([len(p) - 1 for p in prompts], np.int32),
        np.full(len(prompts), new_tokens, np.int32), served, d))
    gaps = got["gap"][got["mask"]]
    emit({"phase": "serve_looped", "attention_impl": impl,
          "num_pages": plan["num_pages"], "engine_page_bytes": page_bytes,
          "wall_seconds_with_compile": round(wall, 1),
          "served_tokens": int(got["mask"].sum()),
          "argmax_share": float((gaps == 0).mean()),
          "worst_gap": float(gaps.max()), "limit": limit_gap,
          "reference_seconds": round(time.time() - t0, 1)})
    check(float(gaps.max()) <= limit_gap,
          f"[looped] a served token lies {float(gaps.max())} below the "
          f"reference's best, over the cell's limit {limit_gap}")
    del params
    free_device_memory()


def phase_serve_swa(seed: int, lengths=(900, 300, 40),
                    new_tokens: int = 24) -> None:
    """The window / full attention decoder (models/laguna.py) at the widths
    of the benchmark's configuration
    (``benchmark/configs/laguna-s-2.1-serve-ep8.json``) and TWO layers of
    each kind (full, sliding, sliding, full: the dense layer and three
    routed ones), served over HTTP by ``LLMEngine`` + ``serve_llm`` on the
    default path: a prompt longer than the window + a chunk (its window
    pages are released behind it while it is prefilled), two that share a
    chunk, then decode ticks. Every served token is held to the plain
    float32 reference (``benchmark/reference/laguna_swa.py``) by the cell's
    own measure and limit."""
    import os
    import jax
    import jax.numpy as jnp
    import numpy as np
    from benchmark import weights_swa
    from benchmark.reference import laguna_swa
    from benchmark.systems import serve_swa
    from paddle_tpu.inference.llm import LLMEngine, serve_llm

    root = os.path.dirname(os.path.abspath(__file__))

    def load(*parts):
        with open(os.path.join(root, "benchmark", *parts)) as f:
            return json.load(f)

    cfg = load("configs", "laguna-s-2.1-serve-ep8.json")
    cfg.update(num_layers=4, layer_types=[
        "full_attention", "sliding_attention", "sliding_attention",
        "full_attention"], num_attention_heads_per_layer=[48, 72, 72, 48])
    limit_gap = load("checks", "agent_closed_swa.json")["worst_gap_limit"]
    d = weights_swa.dims_of(cfg)
    t0 = time.time()
    params = weights_swa.make(d, seed, jnp.bfloat16)
    jax.block_until_ready(params)
    net = serve_swa.build_net(cfg, params)
    net.eval()
    emit({"phase": "serve_swa", "layers": d["kinds"], "heads": d["heads"],
          "hidden": d["H"], "vocab": d["V"], "experts_held": d["count"],
          "params": weights_swa.n_params(d),
          "build_seconds": round(time.time() - t0, 1)})
    prompts = make_prompts(seed, d["V"], lengths, 0)
    t0 = time.time()
    eng = LLMEngine(net, **dict(cfg["engine"], max_seqs=4, max_len=1024,
                                num_pages=257))
    srv = serve_llm(eng)
    try:
        url = "http://%s:%d" % srv.server_address[:2]
        outs = post_all(url, prompts, new_tokens, "swa")
        check(eng.health == "healthy", f"[swa] engine health {eng.health}")
        check("m" in eng.tick_history and "d" in eng.tick_history,
              "[swa] no mixed or no decode tick was dispatched")
        groups = [g.status() for g in eng._pool.groups]
        window = eng._pool.groups[1]
        check(window.n_released > 0 and window.in_use == 0,
              f"[swa] window group: {groups[1]}")
        impl, moe_impl = eng.attention_impl, eng.moe_impl
    finally:
        srv.shutdown()
        srv.server_close()
        eng.close()
    wall = time.time() - t0
    check(not any(o["truncated"] for o in outs), "[swa] truncated")
    del eng, srv, net
    gc.collect()
    t0 = time.time()
    pad = 1024
    ids = np.zeros((len(prompts), pad), np.int32)
    served = np.zeros_like(ids)
    for b, (p, o) in enumerate(zip(prompts, outs)):
        seq = list(p) + list(o["output_ids"])
        ids[b, :len(seq)] = seq
        served[b, len(p) - 1:len(seq) - 1] = o["output_ids"]
    got = jax.device_get(laguna_swa.served_gaps(
        params, ids, np.asarray([len(p) - 1 for p in prompts], np.int32),
        np.full(len(prompts), new_tokens, np.int32), served, d))
    gaps = got["gap"][got["mask"]]
    emit({"phase": "serve_swa", "attention_impl": impl, "moe_impl": moe_impl,
          "cache_groups": groups,
          "wall_seconds_with_compile": round(wall, 1),
          "served_tokens": int(got["mask"].sum()),
          "argmax_share": float((gaps == 0).mean()),
          "worst_gap": float(gaps.max()), "limit": limit_gap,
          "reference_seconds": round(time.time() - t0, 1)})
    check(float(gaps.max()) <= limit_gap,
          f"[swa] a served token lies {float(gaps.max())} below the "
          f"reference's best, over the cell's limit {limit_gap}")
    del params
    free_device_memory()


# ---------------------------------------------------------------------------
# train
# ---------------------------------------------------------------------------

def train_cfg(layers: int, flash: bool, overrides=None):
    from paddle_tpu.models.gpt import gpt_config
    return gpt_config(MODEL, num_layers=layers, hidden_dropout=0.0,
                      attention_dropout=0.0, use_flash=flash,
                      fused_loss=flash, **(overrides or {}))


def build_trainer(seed: int, cfg, mesh=None):
    import paddle_tpu as pt
    from paddle_tpu import parallel
    from paddle_tpu.models.gpt import (GPTForCausalLM,
                                       GPTFusedPretrainingCriterion,
                                       GPTPretrainingCriterion)
    pt.seed(seed)
    net = GPTForCausalLM(cfg)
    model = pt.Model(net)
    model.prepare(
        optimizer=pt.optimizer.AdamW(learning_rate=1e-4, parameters=net,
                                     weight_decay=0.01),
        loss=(GPTFusedPretrainingCriterion() if cfg.fused_loss
              else GPTPretrainingCriterion()),
        amp_configs="O1")
    if mesh is not None:
        parallel.distributed_model(model, mesh=mesh)
    return model


def fit_steps(model, ids, steps: int):
    """``Model.fit`` over ``steps`` copies of one batch; the loss stream."""
    import numpy as np
    from paddle_tpu.hapi.callbacks import Callback
    from paddle_tpu.io import TensorDataset

    class Record(Callback):
        def __init__(self):
            super().__init__()
            self.losses = []

        def on_train_batch_end(self, step, logs=None):
            self.losses.append(float(logs["loss"]))

    rec = Record()
    data = np.tile(ids, (steps, 1))
    t0 = time.time()
    model.fit(TensorDataset([data, data]), batch_size=ids.shape[0],
              epochs=1, verbose=0, shuffle=False, callbacks=[rec])
    return rec.losses, time.time() - t0


def check_losses(losses, what: str) -> None:
    import math
    check(all(math.isfinite(x) for x in losses),
          f"{what}: loss is not finite: {losses}")
    check(losses[-1] < losses[0],
          f"{what}: loss did not fall on a repeated batch: {losses}")


def fit_depth(seed: int, ids, depths, overrides=None):
    """The deepest of ``depths`` whose compiled train step
    (``memory_analysis()``: arguments + temporaries) stays inside HBM_SHARE
    of the device. Returns the prepared model."""
    import jax
    from paddle_tpu.parallel import planner
    limit = hbm(jax.devices()[0])[0]
    tried = []
    for layers in depths:
        model = build_trainer(seed, train_cfg(layers, True, overrides))
        t0 = time.time()
        need = planner.measured_step_bytes(model, (ids,), (ids,))
        tried.append({"layers": layers, "step_bytes": int(need),
                      "compile_seconds": round(time.time() - t0, 1)})
        if need <= limit * HBM_SHARE:
            return model, layers, tried, limit
        del model
        free_device_memory()
    raise SmokeFailure(f"no depth of {list(depths)} fits one chip: {tried}")


def reference_first_loss(seed: int, layers: int, ids, overrides=None):
    """First-step loss of the same model with XLA attention and dense
    logits (use_flash=False, fused_loss=False): the forward loss at the
    seed's weights."""
    model = build_trainer(seed, train_cfg(layers, False, overrides))
    loss = float(model.eval_batch([ids], [ids])["loss"])
    del model
    free_device_memory()
    return loss


def phase_train(seed: int, batch: int = 2, steps: int = 4,
                depths=(14, 12, 10, 8, 6, 4), overrides=None):
    import numpy as np
    from paddle_tpu.models.gpt import PRESETS
    cfg = train_cfg(depths[0], True, overrides)
    seq = cfg.max_position_embeddings
    ids = np.random.RandomState(seed + 2).randint(
        0, cfg.vocab_size, (batch, seq)).astype(np.int32)
    # 16 layers compile to 14.4 GiB on the described v5e (sandbox AOT):
    # over HBM_SHARE of the chip's 15.75 GiB, so the search starts at 14
    model, layers, tried, limit = fit_depth(seed, ids, depths, overrides)
    losses, secs = fit_steps(model, ids, steps)
    del model
    free_device_memory()
    ref = reference_first_loss(seed, layers, ids, overrides)
    rel = abs(losses[0] - ref) / abs(ref)
    emit({"phase": "train", "model": MODEL, "hidden": cfg.hidden_size,
          "heads": cfg.num_heads, "ffn": cfg.ffn_hidden_size,
          "vocab": cfg.vocab_size, "seq": seq, "batch": batch,
          "amp": "O1", "optimizer": "AdamW", "use_flash": True,
          "fused_loss": True,
          "reduced": {"num_layers": [PRESETS[MODEL]["num_layers"], layers]},
          "depth_search": tried, "bytes_limit": limit,
          "hbm_share": HBM_SHARE, "steps": steps, "losses": losses,
          "fit_seconds_with_compile": round(secs, 1),
          "first_loss_no_flash_no_fused": ref,
          "first_loss_rel_diff": round(rel, 6), "rtol": REF_LOSS_RTOL})
    check_losses(losses, "train")
    check(rel <= REF_LOSS_RTOL,
          f"first-step loss {losses[0]} vs {ref} without flash and the "
          f"fused loss: {rel:.4f} > {REF_LOSS_RTOL}")


# ---------------------------------------------------------------------------
# --chips 4: the sharded trainer, and what it is compared with
# ---------------------------------------------------------------------------

def device_bytes():
    import jax
    out = []
    for d in jax.devices():
        s = d.memory_stats() or {}
        out.append({"id": d.id, "bytes_in_use": int(s.get(
            "bytes_in_use", -1)), "peak_bytes_in_use": int(s.get(
                "peak_bytes_in_use", -1))})
    return out


def check_balance(rows, key: str, what: str) -> None:
    vals = [r[key] for r in rows]
    check(min(vals) >= 0, f"{what}: a device reports no {key}")
    mean = sum(vals) / len(vals)
    check(max(vals) <= BALANCE * mean,
          f"{what}: a device holds {max(vals)} B of {key}, more than "
          f"{BALANCE} x the mean {mean:.0f}: {rows}")


def phase_mesh(seed: int, axes=None, steps: int = 3, cut_batch: int = 2,
               full_batch: int = 4, depths=(14, 12, 10, 8, 6, 4),
               full_layers=None, overrides=None) -> None:
    import numpy as np
    from paddle_tpu import parallel
    from paddle_tpu.models.gpt import PRESETS
    axes = axes or {"fsdp": 2, "tp": 2}
    full_layers = full_layers or PRESETS[MODEL]["num_layers"]
    cfg = train_cfg(depths[0], True, overrides)
    seq = cfg.max_position_embeddings
    rng = np.random.RandomState(seed + 2)
    ids = rng.randint(0, cfg.vocab_size, (cut_batch, seq)).astype(np.int32)

    # the comparison: the depth-cut model on one device, then on the mesh
    model, layers, tried, limit = fit_depth(seed, ids, depths, overrides)
    one, one_s = fit_steps(model, ids, steps)
    del model
    free_device_memory()
    check_losses(one, "one device")

    mesh = parallel.init_mesh(**axes)
    try:
        model = build_trainer(seed, train_cfg(layers, True, overrides),
                              mesh=mesh)
        sharded, mesh_s = fit_steps(model, ids, steps)
        cut_bytes = device_bytes()
        del model
        free_device_memory()
        rel = max(abs(a - b) / abs(a) for a, b in zip(one, sharded))
        emit({"phase": "mesh_vs_one_device", "model": MODEL,
              "reduced": {"num_layers": [PRESETS[MODEL]["num_layers"],
                                         layers]},
              "depth_search": tried, "axes": axes, "batch": cut_batch,
              "seq": seq, "steps": steps, "losses_one_device": one,
              "losses_mesh": sharded, "max_rel_diff": round(rel, 6),
              "rtol": MESH_LOSS_RTOL,
              "fit_seconds_with_compile": {"one_device": round(one_s, 1),
                                           "mesh": round(mesh_s, 1)},
              "device_bytes": cut_bytes})
        check_losses(sharded, "mesh")
        check(rel <= MESH_LOSS_RTOL,
              f"one-device and mesh loss streams differ by {rel:.4f} > "
              f"{MESH_LOSS_RTOL}")
        check_balance(cut_bytes, "bytes_in_use", "cut model on the mesh")

        # full depth on the mesh: the configuration that needs four chips
        ids = rng.randint(0, cfg.vocab_size,
                          (full_batch, seq)).astype(np.int32)
        model = build_trainer(seed, train_cfg(full_layers, True, overrides),
                              mesh=mesh)
        losses, secs = fit_steps(model, ids, steps)
        full_bytes = device_bytes()
        emit({"phase": "mesh_full_depth", "model": MODEL,
              "layers": full_layers, "axes": axes, "batch": full_batch,
              "seq": seq, "steps": steps, "losses": losses,
              "fit_seconds_with_compile": round(secs, 1),
              "device_bytes": full_bytes, "bytes_limit": limit,
              "balance": BALANCE})
        check_losses(losses, "full depth on the mesh")
        check_balance(full_bytes, "bytes_in_use", "full depth on the mesh")
        del model
        free_device_memory()
    finally:
        parallel.set_mesh(None)


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--phase", default=None,
                    choices=("kernels", "gdn", "gdn_program", "staging",
                             "serve",
                             "serve_hybrid", "serve_looped", "serve_swa",
                             "train"),
                    help="one chip: run this phase alone (default: all)")
    args = ap.parse_args(argv)
    t0 = time.time()
    try:
        device = phase_device(args.chips)
        if args.chips == 4:
            phase_mesh(args.seed)
        else:
            phases = {"kernels": phase_kernels, "staging": phase_staging,
                      "serve": phase_serve,
                      "serve_hybrid": phase_serve_hybrid,
                      "serve_looped": phase_serve_looped,
                      "serve_swa": phase_serve_swa,
                      "train": phase_train}
            if args.phase == "gdn":
                # (part of the kernels phase; alone, the one-decay delta
                # rule's chunk and step calls)
                time_gdn_chunk(args.seed)
            if args.phase == "gdn_program":
                phase_gdn_program(args.seed)
            for name, phase in phases.items():
                if args.phase in (None, name):
                    phase(args.seed)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    emit({"phase": "compile_cache", **_cache_events,
          "seconds_total": round(time.time() - t0, 1)})
    emit({"ok": True, "device": device})
    return 0


if __name__ == "__main__":
    sys.exit(main())
