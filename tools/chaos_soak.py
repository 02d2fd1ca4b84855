"""Chaos soak gate: drive the stack under seeded fault schedules and
assert the hardened failure semantics hold (tools/ci.sh step).

What it proves (the invariants the multi-node work assumes,
docs/RELIABILITY.md):

1. ENGINE SOAK — an LLMEngine under injected ``device.dispatch`` /
   ``device.transfer`` faults plus a deadline/priority/shed workload:
   every submitted future RESOLVES (value, DeadlineExceeded,
   AdmissionShed, RequestCancelled, or a typed error — never hangs),
   per-request device-retry budgets re-admit faulted requests with
   token-identical streams, and the injected-fault sequence matches
   the pure seeded schedule exactly (same seed → same faults).
2. CANCELLATION STORM — mass ``cancel()`` mid-generation: futures all
   resolve RequestCancelled/result, KV pages are leak-free after
   close, and (with tracing on) no ``llm.*`` span is left open.
3. CRASH-CONSISTENT CHECKPOINTS — a subprocess worker is SIGKILLed
   mid-``CheckpointManager.save``; the directory must still restore
   its latest committed step AND accept new saves. Injected
   ``ckpt.write`` faults are absorbed by the shared retry policy;
   an injected ``ckpt.rename`` (commit-stage) fault fails the save
   call but never corrupts the directory.
4. FLIGHT-RECORDER ESCALATION — a chaos-injected ``io.worker`` fault
   inside ``Model.fit`` escalates to a process crash; the PR-4 flight
   recorder must leave a JSONL dump naming the injected fault.
5. FLEET SOAK (``--fleet``) — a router over K=3 spawned replica
   subprocesses (TCPStore membership): an injected device-fault streak
   drains one replica (router stops admitting to it within a poll
   interval; POST /reset_health recovers it), a SIGKILL mid-decode
   loses ZERO requests (failover re-submits with the same nonce —
   token-identical streams, checked against a reference engine), the
   killed replica's breaker walks open → half-open → closed across a
   respawn, and an injected ``router.dispatch`` fault replays from its
   seed like any other site. Fleet observability rides the same soak:
   ``GET /fleetz`` must aggregate the replicas with per-replica data,
   and an injected DEADLINE-MISS STORM against one SLO class must move
   its multi-window burn-rate gauges on ``GET /sloz`` and latch the
   breach (visible on /healthz, cleared by the reset). On ANY fleet
   assertion failure the report attaches a MERGED cross-process trace
   (router + replicas via tools/trace_merge) next to the fault seed
   and replay command.

8. GOODPUT FORENSICS (default path) — chaos must be visible on the
   time ledger: a seeded ``device.dispatch`` storm grows the
   ``recovery`` bucket on ``GET /goodputz`` and async saves under a
   seeded ``ckpt.async_commit`` fault grow ``ckpt_stall``, with the
   reconciliation line closed throughout; a disabled ledger's
   ``note()`` costs one module-flag check (time-bounded) and records
   nothing.

Determinism: every schedule is nth/probability-based with a fixed
seed; ``faults.preview(site, N)`` recomputes the faulting call
numbers purely, and the soak asserts the observed injection log
equals that schedule.

5b. AUTOSCALE SOAK (``--autoscale``) — the SLO-driven autoscaler over
   a live subprocess fleet (ISSUE 13): a gold-class deadline-miss
   storm trips both burn windows and triggers a scale-out whose first
   spawn attempt dies on the seeded ``autoscale.spawn`` fault (the
   retry absorbs it; the replica counts toward capacity only after
   READY + a successful health probe, and a failed attempt leaves no
   ghost capacity); a SIGKILL of the autoscaled replica mid-decode
   loses ZERO requests (nonce-pinned token-identical failover) and is
   respawned as a REPLACEMENT, not a scale-out; a seeded
   ``autoscale.drain`` fault expires the scale-in drain deadline with
   stragglers in flight, which must complete token-identically on a
   sibling; the terminated replica leaves TCPStore membership
   immediately; both sites replay from the seed. (The static-K vs
   autoscaled replica-seconds/SLO comparison rides
   ``tools/llm_bench.py --ci --storm`` — together they are the
   ISSUE-13 CI gate.)

5c. OVERLOAD SOAK (``--overload``) — the brownout-controller gate
   (ISSUE 20): an in-process two-replica fleet under a seeded 3×
   burst storm of deadline-doomed bronze traffic plus protected gold.
   Every future resolves TYPED (ok / deadline / shed — never error,
   never a hang); gold loses ZERO requests at every ladder level; the
   brownout ladder walks up under burn pressure (one level per
   transition, dwell-bounded) and back to normal after the storm
   drains; a seeded ``overload.estimate`` fault distorts predictions
   1000× and degrades to visible hopeless-shed verdicts; a seeded
   ``overload.step`` fault forces a spurious escalation the
   hysteresis walks back; both sites replay from the seed.

6b. POISONED-STREAM SOAK (rides ``--train``) — the numeric-guard gate
   (ISSUE 9): under a seeded ``data.poison`` / ``grad.nonfinite``
   schedule with the on-device NumericGuard armed (skip policy), the
   final params hex must be BYTE-IDENTICAL to a clean run over the
   same stream with the tripped steps removed, at steps_per_loop ∈
   {1, 4}; the rollback policy must restore a verified checkpoint and
   complete; and guard-off must add zero device work (the lowered
   step program carries no finite-check ops — the one-flag-check
   discipline, plus a wall-clock sanity bound). Assertion failures
   print the fault seed + replay command and attach a flight dump.

6. TRAIN SOAK (``--train``) — the kill-anywhere/resume-exactly gate
   (ISSUE 8): a training worker runs ``Model.fit`` with async
   full-state checkpointing (``checkpoint_dir`` + ``resume="auto"`` +
   ``PreemptionGuard``), announcing phase markers (STEP / SNAPSHOT /
   COMMIT / GC). The parent SIGKILLs it at seeded random points —
   mid-step, mid-snapshot, mid-async-commit, mid-GC — or SIGTERMs it
   (graceful preemption: deadline-budgeted emergency flush, exit 67),
   relaunches until completion, and asserts the combined loss stream
   is BIT-IDENTICAL (float hex) to an uninterrupted baseline at
   ``steps_per_loop`` ∈ {1, 4}, including every re-run overlap step.
   Also: a byte-corrupted newest checkpoint is quarantined on restore
   (falls back to the newest verified step and never surfaces through
   ``latest_step()`` again), ``ckpt.snapshot``/``ckpt.async_commit``
   faults replay from their seed, and an async save's measured
   train-loop stall stays bounded by the device→host snapshot time
   while a (slowed) commit runs in the background.

7. FUSED-SLAB SOAK (``--slab``) — the device-resident decode loop
   (ISSUE 10): the engine scenarios replayed at
   ``decode_ticks_per_dispatch=8`` with the new ``engine.slab`` fault
   site killing slab dispatches on schedule. Every future resolves;
   budgeted retries reproduce streams TOKEN-IDENTICAL to a fault-free
   reference engine (nonce-pinned); deadline/cancel storms landing
   mid-slab resolve typed within a slab boundary with their KV pages
   reclaimed; the injected sequence replays from its seed. Rides
   along: a PAGE-PRESSURE STORM (ISSUE 14) against a tiny pool
   asserting the memory ledger's ``mem_headroom_pages`` gauge hits
   ~0 exactly when slab-shrink engages, the kv_pool attribution rows
   tile the pool at every sampled instant, and headroom recovers to
   the full usable pool after the storm drains (gauge unexported —
   a hole — once the engine closes).

Run:  python tools/chaos_soak.py            # full soak (default seed)
CI:   python tools/chaos_soak.py --ci       # fixed seeds, ~30s budget
      python tools/chaos_soak.py --ci --slab    # fused decode slabs,
                                                # ~30s budget
      python tools/chaos_soak.py --ci --fleet   # replica-kill soak,
                                                # ≤45s budget
      python tools/chaos_soak.py --ci --autoscale  # autoscaler soak,
                                                # ≤90s budget
      python tools/chaos_soak.py --ci --overload  # brownout soak,
                                                # ≤60s budget
      python tools/chaos_soak.py --ci --train   # kill-anywhere train
                                                # soak + poisoned-
                                                # stream guard gate,
                                                # ≤90s budget
Any assertion failure prints the fault seed and the one-line replay
command, so a red CI run reproduces in one copy-paste.
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import subprocess
import sys
import threading
import time
from concurrent.futures import wait as fut_wait

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FUTURE_TIMEOUT = 240.0   # "never hangs" ceiling (compile included)


def _tiny_gpt():
    import paddle_tpu as pt
    from paddle_tpu.models.gpt import GPTForCausalLM, gpt_config
    pt.seed(0)
    cfg = gpt_config("gpt2-small", num_layers=2, hidden_size=64,
                     num_heads=4, vocab_size=97,
                     max_position_embeddings=96, hidden_dropout=0.0,
                     attention_dropout=0.0)
    return GPTForCausalLM(cfg)


def _assert_schedule_matches(faults, sites):
    """The determinism gate: the observed injection log must equal the
    pure seeded schedule truncated to the calls each site actually
    made."""
    log = faults.injected_log()
    assert faults.injected_log_dropped() == 0, (
        "injection log overflowed its bound — raise _LOG_CAP or "
        "shorten the soak; exact-schedule comparison would be "
        "spuriously wrong")
    for site in sites:
        n = faults.call_count(site)
        want = faults.preview(site, n)
        got = [c for s, c in log if s == site]
        assert got == want, (
            f"injected-fault sequence for {site} diverged from the "
            f"seeded schedule: got {got}, schedule {want} "
            f"(over {n} calls)")


def engine_soak(seed: int) -> dict:
    """Scenarios 1 + 2 on one engine (one compile budget): fault soak
    first, then — faults disarmed — a cancellation storm, then the
    leak/span audit after close."""
    from paddle_tpu.inference.llm import (AdmissionShed, AdmissionTimeout,
                                          LLMEngine, RequestCancelled)
    from paddle_tpu.observability import tracing
    from paddle_tpu.reliability import faults
    from paddle_tpu.reliability.retry import DeadlineExceeded

    rng = np.random.RandomState(seed)
    tracing.enable()
    faults.reset()
    faults.enable(seed=seed)
    # schedule: nth/p rules only (pure → previewable). At most 4
    # injections total (2 nth calls + 1 capped p + 1 transfer), so a
    # device_retry_budget of 4 means no request may be LOST to chaos —
    # every non-shed/deadline/cancel future must still produce tokens.
    faults.inject("device.dispatch", nth=(5, 12))
    faults.inject("device.dispatch", p=0.01, times=1)
    faults.inject("device.transfer", nth=(9,))

    net = _tiny_gpt()
    eng = LLMEngine(net, max_seqs=4, page_size=4, num_pages=96,
                    prefill_chunk=16, max_pending=8,
                    admit_timeout=60.0, device_retry_budget=4,
                    drain_after=64)
    outcomes = {"ok": 0, "deadline": 0, "shed": 0, "cancelled": 0,
                "admission_timeout": 0, "error": 0}

    def tally(futs):
        done, not_done = fut_wait(futs, timeout=FUTURE_TIMEOUT)
        assert not not_done, (
            f"{len(not_done)} futures never resolved — the engine "
            f"hung under injected faults")
        for f in futs:
            exc = f.exception()
            if exc is None:
                assert f.result()["output_ids"] is not None
                outcomes["ok"] += 1
            elif isinstance(exc, DeadlineExceeded):
                outcomes["deadline"] += 1
            elif isinstance(exc, AdmissionShed):
                outcomes["shed"] += 1
            elif isinstance(exc, RequestCancelled):
                outcomes["cancelled"] += 1
            elif isinstance(exc, AdmissionTimeout):
                outcomes["admission_timeout"] += 1
            else:
                outcomes["error"] += 1

    try:
        # phase 1: normal service while the fault schedule fires —
        # the retry budget must make chaos invisible in the outcomes
        tally([eng.submit(
            rng.randint(0, 97, rng.randint(3, 12)).tolist(),
            max_new_tokens=int(rng.randint(6, 12)),
            priority=int(i % 3)) for i in range(6)])
        assert outcomes["ok"] == 6, (
            f"requests lost to budgeted chaos: {outcomes}")

        # phase 2: hopeless deadlines resolve typed, never hang
        tally([eng.submit(rng.randint(0, 97, 5).tolist(),
                          max_new_tokens=8, deadline=-1.0)
               for _ in range(3)])
        assert outcomes["deadline"] == 3, outcomes

        # phase 3: a burst wide enough to overflow max_pending=8 on 4
        # slots — overflow sheds, the rest completes
        tally([eng.submit(rng.randint(0, 97, 4).tolist(),
                          max_new_tokens=16) for _ in range(16)])
        assert outcomes["shed"] >= 1, outcomes
        assert outcomes["error"] == 0, (
            f"chaos leaked through the retry budget: {outcomes}")

        _assert_schedule_matches(
            faults, ("device.dispatch", "device.transfer"))
        n_injected = len(faults.injected_log())
        assert n_injected >= 3, (
            f"schedule armed but only {n_injected} faults injected — "
            f"the soak did not exercise the failure paths")

        # phase 4: cancellation storm, faults off
        faults.disable()
        eng.reset_health()
        storm = [eng.submit(rng.randint(0, 97, 6).tolist(),
                            max_new_tokens=80) for _ in range(8)]
        # half cancelled immediately (microseconds after submit — a
        # cancel can only miss if the request fully generated first,
        # impossible for 80 tokens), half after some reach decode
        for f in storm[::2]:
            eng.cancel(f.request_id)
        time.sleep(0.2)
        for f in storm[1::2]:
            eng.cancel(f.request_id)
        done, not_done = fut_wait(storm, timeout=FUTURE_TIMEOUT)
        assert not not_done, "cancellation storm left futures pending"
        n_cancelled = 0
        for f in storm:
            exc = f.exception()
            assert exc is None or isinstance(exc, RequestCancelled), exc
            n_cancelled += exc is not None
        outcomes["cancelled"] += n_cancelled
        assert n_cancelled >= 1, "storm cancelled nothing"
    finally:
        eng.close()
        faults.reset()
    # leak audit: every page back in the pool after close (the prefix
    # cache was flushed; shared pages returned)
    assert len(eng._free_pages) == eng.num_pages - 1, (
        f"KV pages leaked: {len(eng._free_pages)} free of "
        f"{eng.num_pages - 1} usable")
    # span audit: no llm.* span left open anywhere
    open_llm = [s for s in tracing.live_spans()
                if s["name"].startswith("llm.")]
    tracing.disable()
    assert not open_llm, f"span trees left open: {open_llm}"
    return outcomes


def slab_soak(seed: int, kv_dtype=None) -> dict:
    """ISSUE 10 phase: the engine invariants under FUSED DECODE SLABS
    (``decode_ticks_per_dispatch=8``) — an injected ``engine.slab``
    kill storm at the slab dispatch (pure-decode AND the ragged mixed
    slab that carries the prompts), hopeless deadlines, and a
    cancellation storm landing mid-slab. Asserts: every future
    resolves; retried streams are TOKEN-IDENTICAL to a fault-free
    reference engine over the same prompts (device retries keep the
    nonce, and a slab re-admission replays the same sampled stream);
    deadlines/cancels resolve typed within a slab boundary; zero KV
    pages leak and no ``llm.*`` span stays open after close; the
    injected sequence equals the pure seeded schedule.

    ISSUE 15 rider (``kv_dtype="int8"``): the SAME storm on an
    int8-quantized pool — nonce-pinned token identity must hold
    against an int8 reference (quantization is deterministic, so
    chaos stays invisible in the streams)."""
    from paddle_tpu.inference.llm import LLMEngine, RequestCancelled
    from paddle_tpu.observability import tracing
    from paddle_tpu.reliability import faults
    from paddle_tpu.reliability.retry import DeadlineExceeded

    rng = np.random.RandomState(seed)
    prompts = [rng.randint(0, 97, int(rng.randint(3, 12))).tolist()
               for _ in range(6)]
    gens = [int(rng.randint(8, 20)) for _ in range(6)]
    net = _tiny_gpt()

    def build(**kw):
        return LLMEngine(net, max_seqs=4, page_size=4, num_pages=96,
                         prefill_chunk=16, drain_after=64,
                         decode_ticks_per_dispatch=8,
                         kv_dtype=kv_dtype, **kw)

    # fault-free reference streams: same engine seed, same submission
    # order => same nonces => the chaos run must reproduce these
    # exactly even when its slabs die and re-admit
    with build() as ref_eng:
        ref = [f.result(timeout=FUTURE_TIMEOUT) for f in
               [ref_eng.submit(p, max_new_tokens=g)
                for p, g in zip(prompts, gens)]]
    assert len(ref_eng._free_pages) == ref_eng.num_pages - 1

    tracing.enable()
    faults.reset()
    faults.enable(seed=seed)
    # at most 4 injections (2 nth + 1 capped p at the slab dispatch +
    # 1 transfer) against a retry budget of 4: chaos must be invisible
    # in the outcomes AND in the token streams
    faults.inject("engine.slab", nth=(2, 5))
    faults.inject("engine.slab", p=0.02, times=1)
    faults.inject("device.transfer", nth=(7,))
    eng = build(device_retry_budget=4, admit_timeout=60.0)
    try:
        futs = [eng.submit(p, max_new_tokens=g)
                for p, g in zip(prompts, gens)]
        done, not_done = fut_wait(futs, timeout=FUTURE_TIMEOUT)
        assert not not_done, (
            f"{len(not_done)} futures never resolved — the engine "
            f"hung under injected slab faults")
        for f, r in zip(futs, ref):
            assert f.exception() is None, (
                f"request lost to budgeted slab chaos: {f.exception()}")
            assert f.result()["output_ids"] == r["output_ids"], (
                "retried slab stream diverged from the fault-free "
                "reference (nonce-pinned token identity broken)")
        n_injected = len(faults.injected_log())
        assert n_injected >= 2, (
            f"schedule armed but only {n_injected} faults injected — "
            f"the soak did not exercise the slab failure path")
        _assert_schedule_matches(
            faults, ("engine.slab", "device.transfer"))

        # hopeless deadlines resolve typed (at a slab boundary)
        dl = [eng.submit(rng.randint(0, 97, 5).tolist(),
                         max_new_tokens=8, deadline=-1.0)
              for _ in range(3)]
        done, not_done = fut_wait(dl, timeout=FUTURE_TIMEOUT)
        assert not not_done, "deadline futures pending under slabs"
        assert all(isinstance(f.exception(), DeadlineExceeded)
                   for f in dl), [f.exception() for f in dl]

        # cancellation storm, faults off: cancels land mid-slab and
        # must resolve at the boundary with pages reclaimed
        faults.disable()
        eng.reset_health()
        storm = [eng.submit(rng.randint(0, 97, 6).tolist(),
                            max_new_tokens=80) for _ in range(8)]
        for f in storm[::2]:
            eng.cancel(f.request_id)
        time.sleep(0.2)
        for f in storm[1::2]:
            eng.cancel(f.request_id)
        done, not_done = fut_wait(storm, timeout=FUTURE_TIMEOUT)
        assert not not_done, (
            "cancellation storm left futures pending under fused "
            "slabs")
        n_cancelled = 0
        for f in storm:
            exc = f.exception()
            assert exc is None or isinstance(exc, RequestCancelled), \
                exc
            n_cancelled += exc is not None
        assert n_cancelled >= 1, "storm cancelled nothing"
    finally:
        eng.close()
        faults.reset()
    assert len(eng._free_pages) == eng.num_pages - 1, (
        f"KV pages leaked under fused slabs: "
        f"{len(eng._free_pages)} free of {eng.num_pages - 1} usable")
    open_llm = [s for s in tracing.live_spans()
                if s["name"].startswith("llm.")]
    tracing.disable()
    assert not open_llm, f"span trees left open: {open_llm}"
    return {"injected": n_injected, "cancelled": n_cancelled,
            "requests": len(futs) + len(dl) + len(storm),
            "kv_dtype": kv_dtype or "f32"}


def spec_slab_soak(seed: int) -> dict:
    """ISSUE 17 rider (rides --slab): the SAME kill/cancel/deadline
    storm with a DRAFT ENGINE running on-device speculative rounds
    (prefix cache + int8 quantized draft pool + fused N=8 slabs all
    on). Asserts: every future resolves under an
    ``engine.slab`` storm at the spec dispatch within
    ``device_retry_budget``; retried streams — greedy AND
    temperature>0 — are TOKEN-IDENTICAL to a fault-free spec
    reference (keys fold (nonce, position) only, so a re-admitted
    slot replays its rejection-sampling decisions exactly);
    rejected-draft pages cannot leak through a cancellation storm
    (the draft pool shares the target's block tables — one audit
    covers both); the injected sequence equals the pure seeded
    schedule."""
    import paddle_tpu as pt
    from paddle_tpu.inference.llm import LLMEngine, RequestCancelled
    from paddle_tpu.models.gpt import GPTForCausalLM, gpt_config
    from paddle_tpu.observability import tracing
    from paddle_tpu.reliability import faults
    from paddle_tpu.reliability.retry import DeadlineExceeded

    rng = np.random.RandomState(seed)
    prompts = [rng.randint(0, 97, int(rng.randint(3, 12))).tolist()
               for _ in range(6)]
    gens = [int(rng.randint(8, 20)) for _ in range(6)]
    temps = [0.0, 0.0, 0.8, 0.0, 0.8, 0.0]
    net = _tiny_gpt()
    pt.seed(321)
    dcfg = gpt_config("gpt2-small", num_layers=1, hidden_size=32,
                      num_heads=2, vocab_size=97,
                      max_position_embeddings=96, hidden_dropout=0.0,
                      attention_dropout=0.0)
    draft = GPTForCausalLM(dcfg)

    def build(**kw):
        return LLMEngine(net, max_seqs=4, page_size=4, num_pages=96,
                         prefill_chunk=16, drain_after=64,
                         decode_ticks_per_dispatch=8,
                         draft_net=draft, spec_tokens=3,
                         kv_dtype="int8", **kw)

    # fault-free spec reference: same engine seed, same submission
    # order => same nonces => the chaos run must reproduce these
    # exactly even when its spec slabs die and re-admit
    with build() as ref_eng:
        ref = [f.result(timeout=FUTURE_TIMEOUT) for f in
               [ref_eng.submit(p, max_new_tokens=g, temperature=t)
                for p, g, t in zip(prompts, gens, temps)]]
        assert ref_eng.n_spec_rounds > 0, \
            "spec reference never ran a speculative round"

    tracing.enable()
    faults.reset()
    faults.enable(seed=seed)
    faults.inject("engine.slab", nth=(2, 5))
    faults.inject("engine.slab", p=0.02, times=1)
    faults.inject("device.transfer", nth=(7,))
    eng = build(device_retry_budget=4, admit_timeout=60.0)
    try:
        futs = [eng.submit(p, max_new_tokens=g, temperature=t)
                for p, g, t in zip(prompts, gens, temps)]
        done, not_done = fut_wait(futs, timeout=FUTURE_TIMEOUT)
        assert not not_done, (
            f"{len(not_done)} futures never resolved — the spec "
            f"engine hung under injected slab faults")
        for f, r in zip(futs, ref):
            assert f.exception() is None, (
                f"request lost to budgeted spec-slab chaos: "
                f"{f.exception()}")
            assert f.result()["output_ids"] == r["output_ids"], (
                "retried spec stream diverged from the fault-free "
                "reference (nonce-pinned token identity broken)")
        n_injected = len(faults.injected_log())
        assert n_injected >= 2, (
            f"schedule armed but only {n_injected} faults injected — "
            f"the soak did not exercise the spec-slab failure path")
        _assert_schedule_matches(
            faults, ("engine.slab", "device.transfer"))

        # hopeless deadlines resolve typed (at a slab boundary)
        dl = [eng.submit(rng.randint(0, 97, 5).tolist(),
                         max_new_tokens=8, deadline=-1.0)
              for _ in range(3)]
        done, not_done = fut_wait(dl, timeout=FUTURE_TIMEOUT)
        assert not not_done, "deadline futures pending under spec slabs"
        assert all(isinstance(f.exception(), DeadlineExceeded)
                   for f in dl), [f.exception() for f in dl]

        # cancellation storm, faults off: cancels land mid-slab with
        # rejected draft KV in flight — pages must all come back
        faults.disable()
        eng.reset_health()
        storm = [eng.submit(rng.randint(0, 97, 6).tolist(),
                            max_new_tokens=80) for _ in range(8)]
        for f in storm[::2]:
            eng.cancel(f.request_id)
        time.sleep(0.2)
        for f in storm[1::2]:
            eng.cancel(f.request_id)
        done, not_done = fut_wait(storm, timeout=FUTURE_TIMEOUT)
        assert not not_done, (
            "cancellation storm left futures pending under spec "
            "slabs")
        n_cancelled = 0
        for f in storm:
            exc = f.exception()
            assert exc is None or isinstance(exc, RequestCancelled), \
                exc
            n_cancelled += exc is not None
        assert n_cancelled >= 1, "storm cancelled nothing"
    finally:
        eng.close()
        faults.reset()
    assert len(eng._free_pages) == eng.num_pages - 1, (
        f"KV pages leaked through rejected-draft rounds: "
        f"{len(eng._free_pages)} free of {eng.num_pages - 1} usable")
    open_llm = [s for s in tracing.live_spans()
                if s["name"].startswith("llm.")]
    tracing.disable()
    assert not open_llm, f"span trees left open: {open_llm}"
    return {"injected": n_injected, "cancelled": n_cancelled,
            "requests": len(futs) + len(dl) + len(storm),
            "spec_rounds": eng.n_spec_rounds,
            "accept_rate": round(eng.n_spec_accepted /
                                 max(1, eng.n_spec_proposed), 3)}


def page_pressure_soak(seed: int, kv_dtype=None) -> dict:
    """ISSUE 14 phase (rides --slab): a PAGE-PRESSURE STORM against a
    deliberately tiny KV pool, polling the memory ledger's headroom
    while fused slabs fight the allocator. Asserts the accounting
    closes the loop: the ``mem_headroom_pages`` gauge hits ~0 exactly
    when slab-shrink engages (a slab truncating at ``covered == 0``
    IS the allocator returning None, i.e. headroom 0 at that entry —
    witnessed here by truncated results + a shrunk ``decode_loop``
    signature + the polled gauge minimum), the kv_pool ledger rows
    tile the pool exactly at every sampled instant, and headroom
    RECOVERS to the full usable pool after the storm drains.

    ISSUE 15 rider (``kv_dtype="int8"``): the SAME storm at the SAME
    pool HBM budget — int8 pages (scale tables included) must buy
    >= 1.8x the f32 pages, the kv_pool rows now include the distinct
    ``scale_table`` kind and STILL tile the pool exactly, and the
    headroom gauge semantics re-pin unchanged (the storm is doubled
    so the bigger pool still runs dry and slab-shrink engages)."""
    from paddle_tpu.inference.llm import LLMEngine
    from paddle_tpu.observability import memory as memobs
    from paddle_tpu.observability.metrics import default_registry

    rng = np.random.RandomState(seed)
    net = _tiny_gpt()
    N = 8
    # 17 usable pages of 4 tokens: 4 slots x (2 prompt pages + up to
    # 2 slab pages per dispatch) oversubscribes the pool by design.
    # The int8 rider holds the HBM BUDGET fixed (18 f32 pages' worth)
    # and lets the quantized pool claim however many pages fit.
    num_pages, n_requests = 18, 8
    if kv_dtype is not None:
        probe = LLMEngine(net, max_seqs=2, page_size=4, num_pages=8,
                          prefill_chunk=16, max_len=64)
        budget = 18 * probe._page_bytes
        probe.close()
        probe = LLMEngine(net, max_seqs=2, page_size=4, num_pages=8,
                          prefill_chunk=16, max_len=64,
                          kv_dtype=kv_dtype)
        num_pages = int(budget // probe._page_bytes)
        probe.close()
        assert num_pages - 1 >= 1.8 * 17, (
            f"kv_dtype={kv_dtype} bought only {num_pages - 1} usable "
            f"pages at the 17-page f32 HBM budget (<1.8x)")
        n_requests = 16   # double the storm: the bigger pool must
        #                   still run dry for the shrink pin to hold
    # the ~2x-occupancy witness: the int8 run serves DOUBLE the
    # concurrent slots at the same pool HBM — 4 f32 slots' full need
    # oversubscribes 17 pages, 8 int8 slots' oversubscribes its ~2x
    # pool, so slab-shrink engages at twice the occupancy
    max_seqs = 4 if kv_dtype is None else 8
    eng = LLMEngine(net, max_seqs=max_seqs, page_size=4,
                    num_pages=num_pages, prefill_chunk=16,
                    max_len=64, decode_ticks_per_dispatch=N,
                    admit_timeout=120.0, kv_dtype=kv_dtype)
    led = memobs.instance()
    usable = eng.num_pages - 1
    samples = []
    stop = threading.Event()

    def poll():
        while not stop.is_set():
            h = led.headroom()
            rows = {r["kind"]: r["bytes"] for r in led.rows()
                    if r["owner"] == "kv_pool"}
            if h is not None and rows:
                led.update_gauges()
                g = default_registry().get("mem_headroom_pages")
                samples.append((h["kv_pages_addable"],
                                g.value if g is not None else None,
                                sum(rows.values())))
            time.sleep(0.001)

    poller = threading.Thread(target=poll, daemon=True)
    poller.start()
    try:
        # int8 rider: a 10-token prompt leaves decode mid-page, so a
        # dry pool yields a PARTIAL coverage (slab shrink) rather
        # than only boundary truncations — the shrink pin stays
        # deterministic at the doubled occupancy
        plen = 8 if kv_dtype is None else 10
        futs = [eng.submit(rng.randint(0, 97, plen).tolist(),
                           max_new_tokens=40)
                for _ in range(n_requests)]
        done, not_done = fut_wait(futs, timeout=FUTURE_TIMEOUT)
        assert not not_done, "futures pending under page pressure"
        outs = [f.result() for f in futs]
    finally:
        stop.set()
        poller.join(timeout=10)
    n_trunc = sum(o["truncated"] for o in outs)
    assert n_trunc >= 1, (
        "the storm never hit page pressure — shrink/truncation path "
        "unexercised (grow max_new_tokens or shrink num_pages)")
    shrunk = any(k[0] == "decode_loop" and k[1] < N
                 for k in eng._shape_signatures)
    assert shrunk, (
        f"no shrunk decode_loop signature compiled — the slab never "
        f"hit the coverable boundary: {sorted(eng._shape_signatures)}")
    assert samples, "ledger poller captured nothing"
    min_head = min(s[0] for s in samples)
    min_gauge = min(s[1] for s in samples if s[1] is not None)
    assert min_head <= 1, (
        f"headroom never approached 0 under a pool-exhausting storm "
        f"(min {min_head} of {usable} usable)")
    assert min_gauge <= 1, (
        f"mem_headroom_pages gauge never approached 0 (min "
        f"{min_gauge})")
    # attribution exactness held at EVERY sampled instant: the
    # free/private/shared/scratch (+ scale_table under int8) rows
    # tile the pool — page bytes INCLUDE the scale tables
    pool_bytes = eng.num_pages * eng._page_bytes
    bad = [s for s in samples if s[2] != pool_bytes]
    assert not bad, (
        f"kv_pool ledger rows stopped tiling the pool at "
        f"{len(bad)}/{len(samples)} samples: {bad[:3]}")
    if kv_dtype == "int8":
        rows = {r["kind"] for r in led.rows()
                if r["owner"] == "kv_pool"}
        assert "scale_table" in rows, (
            f"int8 pool reported no scale_table ledger row: {rows}")
    # drained: every page is free or an evictable cache resident again
    h = led.headroom()
    assert h is not None and h["kv_pages_addable"] == usable, (
        f"headroom did not recover after drain: {h} vs {usable}")
    eng.close()
    assert led.headroom() is None, \
        "closed engine still reports pool headroom (stale provider)"
    led.update_gauges()
    assert default_registry().get("mem_headroom_pages") is None, \
        "mem_headroom_pages gauge survived the last pool's close"
    return {"requests": len(outs), "truncated": n_trunc,
            "min_headroom": min_head, "samples": len(samples),
            "kv_dtype": kv_dtype or "f32", "usable_pages": usable}


def ckpt_crash(seed: int, workdir: str) -> dict:
    """Scenario 3: SIGKILL a worker mid-save, then prove the directory
    restores cleanly and still accepts saves; then the injected-fault
    variants of the same invariant."""
    from paddle_tpu.io.checkpoint import CheckpointManager
    from paddle_tpu.reliability import faults
    from paddle_tpu.reliability.faults import FaultInjected

    rng = np.random.RandomState(seed)
    ckpt_dir = os.path.join(workdir, "ckpt_kill")
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    kill_at = int(rng.randint(2, 5))
    p = subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), "--ckpt-worker",
         ckpt_dir, "12"],
        env=env, cwd=REPO, stdout=subprocess.PIPE, text=True)
    killed_during = None
    for line in p.stdout:
        if line.startswith("SAVING "):
            k = int(line.split()[1])
            if k >= kill_at:
                # land the SIGKILL inside the save window (the worker
                # announces, then saves); a seeded jitter moves the
                # kill around within it across seeds
                time.sleep(float(rng.uniform(0.0, 0.05)))
                p.kill()
                killed_during = k
                break
    p.wait(timeout=60)
    assert killed_during is not None, "worker finished before the kill"

    mgr = CheckpointManager(ckpt_dir, async_save=False)
    latest = mgr.latest_step()
    assert latest is not None and latest >= killed_during - 1, (
        f"mid-save SIGKILL lost committed steps: latest={latest}, "
        f"killed during save of {killed_during}")
    tree = mgr.restore(latest)
    np.testing.assert_array_equal(
        tree["w"], np.arange(2048, dtype=np.int64) + latest)
    # the survivor directory still accepts new saves (tmp-dir debris
    # from the kill must not wedge the next incarnation)
    assert mgr.save(latest + 1, {"w": np.arange(2048) + latest + 1,
                                 "step": np.asarray(latest + 1)})
    mgr.wait_until_finished()
    mgr.close()

    # injected ckpt.write faults: absorbed by the shared retry policy
    faults.reset()
    faults.enable(seed=seed)
    faults.inject("ckpt.write", nth=(1,), times=1)
    retry_dir = os.path.join(workdir, "ckpt_retry")
    with CheckpointManager(retry_dir, async_save=False) as m2:
        assert m2.save(0, {"w": np.arange(16)})
        m2.wait_until_finished()
        assert m2.latest_step() == 0
    assert ("ckpt.write", 1) in faults.injected_log()

    # injected ckpt.rename (commit-stage) fault: the save CALL fails,
    # the directory stays restorable
    faults.inject("ckpt.rename", nth=(faults.call_count("ckpt.rename")
                                      + 1,), times=1)
    with CheckpointManager(retry_dir, async_save=False) as m3:
        try:
            m3.save(1, {"w": np.arange(16) + 1})
            raised = False
        except FaultInjected:
            raised = True
        assert raised, "ckpt.rename fault did not surface"
        m3.wait_until_finished()
        latest = m3.latest_step()
        assert latest is not None
        np.testing.assert_array_equal(
            m3.restore(latest)["w"], np.arange(16) + latest)
    faults.reset()
    return {"killed_during": killed_during, "latest": int(latest)}


def flight_escalation(seed: int, workdir: str) -> dict:
    """Scenario 4: an injected io.worker fault inside Model.fit goes
    uncaught, the process dies, and the flight recorder dumps."""
    crash_dir = os.path.join(workdir, "flight")
    code = f"""
import jax; jax.config.update("jax_platforms", "cpu")
import numpy as np
import paddle_tpu as pt
from paddle_tpu import nn
from paddle_tpu.io import TensorDataset
from paddle_tpu.observability import flight, tracing
from paddle_tpu.reliability import faults
tracing.enable()
flight.install_flight_recorder({crash_dir!r})
faults.enable(seed={seed})
faults.inject("io.worker", nth=(3,))
pt.seed(0)
net = nn.Sequential(nn.Linear(8, 16), nn.ReLU(), nn.Linear(16, 2))
model = pt.Model(net)
model.prepare(optimizer=pt.optimizer.SGD(learning_rate=0.1,
                                         parameters=net),
              loss=nn.CrossEntropyLoss())
x = np.zeros((64, 8), np.float32)
y = np.zeros((64, 1), np.int64)
model.fit(TensorDataset([x, y]), batch_size=8, epochs=2, verbose=0)
raise SystemExit("unreachable: the injected fault must escalate")
"""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    p = subprocess.run([sys.executable, "-c", code], env=env, cwd=REPO,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0, (
        "chaos-injected io.worker fault did not crash the run:\n"
        + p.stdout[-400:] + p.stderr[-400:])
    assert "injected fault at io.worker" in p.stderr, p.stderr[-800:]
    dumps = sorted(f for f in os.listdir(crash_dir)
                   if f.endswith(".jsonl"))
    assert dumps, "flight recorder wrote no dump for the chaos crash"
    rows = [json.loads(ln)
            for ln in open(os.path.join(crash_dir, dumps[0]))]
    assert rows[0]["kind"] == "header", rows[0]
    assert rows[0]["reason"] == "exception", rows[0]
    return {"dump": dumps[0], "rows": len(rows)}


def goodput_soak(seed: int, workdir: str) -> dict:
    """Scenario 8: goodput-ledger forensics under chaos. The seeded
    fault storms must be VISIBLE on ``GET /goodputz``: a
    ``device.dispatch`` storm grows the ``recovery`` bucket (the
    window spent on a failed device call is recovery badput), and an
    async-checkpoint run under a seeded ``ckpt.async_commit`` fault
    still grows ``ckpt_stall`` by its snapshot windows (the only
    phase the train loop waits on — the commit fault surfaces at the
    barrier, never in the stall accounting). The reconciliation line
    must stay closed throughout. Then the off-switch pin: with the
    ledger disabled, ``note()`` must cost one module-flag check
    (time-bounded, the PR-4 tracing discipline) and record nothing."""
    from urllib.request import urlopen

    from paddle_tpu.inference.llm import LLMEngine
    from paddle_tpu.io.checkpoint import CheckpointManager
    from paddle_tpu.observability import goodput
    from paddle_tpu.observability.server import DebugServer
    from paddle_tpu.reliability import faults
    from paddle_tpu.reliability.faults import FaultInjected

    assert goodput.enabled(), "goodput ledger disabled in the soak env"
    rng = np.random.RandomState(seed)
    dbg = DebugServer(port=0).start()
    base = f"http://127.0.0.1:{dbg.port}"

    def goodputz():
        with urlopen(base + "/goodputz", timeout=10) as r:
            return json.loads(r.read())

    out = {}
    try:
        g0 = goodputz()["buckets"]

        # -- phase A: device.dispatch storm → recovery badput ---------
        faults.reset()
        faults.enable(seed=seed)
        # faults land AFTER the first fetches (the recovery window is
        # measured from the last drained fetch — a fault before any
        # fetch has no attributable start)
        faults.inject("device.dispatch", nth=(5, 12))
        net = _tiny_gpt()
        with LLMEngine(net, max_seqs=4, page_size=4, num_pages=96,
                       prefill_chunk=16, device_retry_budget=4,
                       admit_timeout=60.0) as eng:
            futs = [eng.submit(rng.randint(0, 97, 8).tolist(),
                               max_new_tokens=8) for _ in range(6)]
            done, not_done = fut_wait(futs, timeout=FUTURE_TIMEOUT)
            assert not not_done, "futures pending under the storm"
            for f in futs:
                assert f.exception() is None, f.exception()
        n_dispatch = sum(1 for s, _ in faults.injected_log()
                         if s == "device.dispatch")
        assert n_dispatch >= 2, faults.injected_log()
        faults.reset()
        g1 = goodputz()["buckets"]
        assert g1["recovery"] > g0["recovery"], (
            f"a {n_dispatch}-fault dispatch storm left the recovery "
            f"bucket flat: {g0} -> {g1}")
        assert g1["productive"] > g0["productive"], (g0, g1)

        # -- phase B: async saves under a seeded commit fault →
        # ckpt_stall moves by the snapshot windows
        faults.enable(seed=seed)
        faults.inject("ckpt.async_commit", nth=(2,), times=1)
        d = os.path.join(workdir, "goodput_ck")
        mgr = CheckpointManager(d, async_save=True)
        try:
            mgr.save(1, {"w": np.zeros((256, 256), np.float32)})
            mgr.wait_until_finished()
            try:
                mgr.save(2, {"w": np.zeros((256, 256), np.float32)})
                mgr.wait_until_finished()
                raised = False
            except FaultInjected:
                raised = True
            assert raised, "ckpt.async_commit fault did not surface"
        finally:
            mgr.close()
            faults.reset()
        gz = goodputz()
        g2 = gz["buckets"]
        assert g2["ckpt_stall"] > g1["ckpt_stall"], (
            f"two async saves left the ckpt_stall bucket flat: "
            f"{g1} -> {g2}")
        rec = gz["reconciliation"]
        assert abs(rec["residual_s"]) < 1e-6, rec
        out["buckets"] = {k: round(v, 4) for k, v in g2.items() if v}

        # -- phase C: ledger-off = one module-flag check --------------
        goodput.disable()
        try:
            led = goodput.instance()
            before = led.totals()["productive"]
            n_calls = 200_000
            t0 = time.perf_counter()
            for _ in range(n_calls):
                goodput.note("productive", 1.0)
            per_call = (time.perf_counter() - t0) / n_calls
            assert per_call < 5e-6, (
                f"disabled goodput.note costs "
                f"{per_call * 1e6:.2f}us/call — more than a flag "
                f"check")
            assert led.totals()["productive"] == before, (
                "disabled ledger still recorded intervals")
            assert goodputz()["enabled"] is False
        finally:
            goodput.enable()
        out["off_ns_per_call"] = round(per_call * 1e9)
    finally:
        faults.reset()
        dbg.stop()
    return out


def _poll_until(fn, timeout: float, what: str):
    """Poll ``fn`` (returns falsy to keep waiting) with a bounded
    budget; returns its first truthy value."""
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        v = fn()
        if v:
            return v
        time.sleep(0.05)
    raise AssertionError(f"timed out ({timeout}s) waiting for {what}")


def _attach_fleet_trace(workdir: str, infos: dict):
    """Best-effort failure attachment: merge the router's span table,
    every reachable replica's /tracez, and any flight dumps under the
    soak's obs_dir into one chrome trace. Never raises — the original
    assertion is the story; this is the supporting evidence."""
    try:
        from paddle_tpu.observability import tracing
        from tools.trace_merge import load_source, merge_chrome_trace
        wall = tracing.perf_to_wall
        sources = {"router": (
            [dict(s, ts_wall=wall(s["ts"]), live=False)
             for s in tracing.finished_spans()]
            + [dict(s, ts_wall=wall(s["ts"]), live=True)
               for s in tracing.live_spans()])}
        for n, info in infos.items():
            url = info.get("tracez")
            if not url:
                continue
            try:
                sources[n] = load_source(url, timeout=5)
            except Exception:  # noqa: BLE001 — a dead replica's live
                pass           # table is gone; its flight dump below
        obs_dir = os.path.join(workdir, "obs")
        if os.path.isdir(obs_dir):
            for root, _dirs, files in os.walk(obs_dir):
                for fn in files:
                    if fn.startswith("flight_") and \
                            fn.endswith(".jsonl"):
                        tag = f"{os.path.basename(root)}:{fn}"
                        try:
                            sources[tag] = load_source(
                                os.path.join(root, fn))
                        except Exception:  # noqa: BLE001
                            pass
        path = os.path.join(workdir, "fleet_failure_trace.json")
        return path, merge_chrome_trace(sources, path)
    except Exception:  # noqa: BLE001 — never mask the real failure
        return None, None


def fleet_soak(seed: int, workdir: str) -> dict:
    """Scenario 5: the serving fleet under replica-level chaos.
    Asserts the ISSUE-6 acceptance invariants: zero lost requests
    across a SIGKILL (token-identical failover within budget), breaker
    open → half-open → closed across a respawn, draining replicas
    receiving no new admissions within one health-poll interval, and
    seed-replayable router fault sites — plus the ISSUE-7 fleet
    observability invariants (/fleetz aggregation, /sloz burn rates
    moving under a deadline-miss storm, cross-process traces)."""
    from paddle_tpu.distributed.tcp_store import TCPStoreServer
    from paddle_tpu.observability import tracing
    from paddle_tpu.reliability import faults
    from paddle_tpu.serving import (LocalReplica, Router, SLOClass,
                                    make_engine_from_spec,
                                    spawn_replica)
    from paddle_tpu.serving.router import affinity_key, rendezvous_pick

    rng = np.random.RandomState(seed)
    faults.reset()
    tracing.enable()   # router-side spans: the failure report's raw data
    store = TCPStoreServer("127.0.0.1", 0)
    endpoint = f"127.0.0.1:{store.port}"
    obs_dir = os.path.join(workdir, "obs")
    model = {"platform": "cpu",   # a CPU-only gate by design
             "vocab": 97, "layers": 2, "hidden": 64, "heads": 4,
             "max_pos": 96, "model_seed": 0,
             # every replica traces and collects its dumps under ONE
             # tree the soak can merge on failure
             "tracing": True, "obs_dir": obs_dir}
    engine_kw = {"device_retry_budget": 2, "drain_after": 2,
                 "max_pending": 64, "seed": 0}
    names = ("r0", "r1", "r2")
    specs = {n: dict(model, name=n, store=endpoint,
                     engine=dict(engine_kw)) for n in names}
    # r2's schedule: dispatch calls 3 and 4 fault back-to-back — two
    # CONSECUTIVE device errors at drain_after=2 latch it DRAINING
    # while its first request is in flight (the request survives via
    # the engine retry budget → draining shed → router rebalance)
    specs["r2"]["faults"] = {"seed": seed, "rules": [
        {"site": "device.dispatch", "nth": [3, 4]}]}

    procs, infos = {}, {}

    def _spawn(name):
        procs[name], infos[name] = spawn_replica(specs[name],
                                                 timeout=180)

    # STAGGERED spawn: r0 comes up alone and serves one warm request,
    # populating the shared persistent compile cache; r1/r2 (and the
    # parent's reference engine) then hit its artifacts instead of
    # compiling the same programs 3x on a contended host
    _spawn("r0")
    from paddle_tpu.serving import HTTPReplica
    HTTPReplica(infos["r0"]["generate"],
                infos["r0"]["healthz"]).submit([1, 2, 3],
                                               max_new_tokens=2)
    threads = [threading.Thread(target=_spawn, args=(n,))
               for n in ("r1", "r2")]
    for t in threads:
        t.start()
    # the reference engine (same weights/seed as every replica)
    # replays failover'd requests to pin token identity; it reads the
    # same compile cache
    from paddle_tpu.core import compile_cache
    compile_cache.enable()
    ref = LocalReplica(make_engine_from_spec(dict(model,
                                                  engine=engine_kw)))
    ref_warm = threading.Thread(
        target=lambda: ref.submit([1, 2, 3], max_new_tokens=1))
    ref_warm.start()
    for t in threads:
        t.join(timeout=240)
    assert set(infos) == set(names), f"replicas failed to spawn: " \
        f"{sorted(set(names) - set(infos))}"

    router = Router(store_endpoint=endpoint, page_size=16,
                    affinity_pages=2, failover_budget=2,
                    health_poll_interval=0.2,
                    membership_stale_after=1.5,
                    breaker_fail_threshold=3, breaker_open_for=1.0,
                    # the SLO class the phase-D deadline-miss storm
                    # burns: tight windows so a ~45s soak spans them
                    slo_classes={"gold": SLOClass(
                        "gold", deadline_s=60.0, target=0.99)},
                    slo_windows=(2.0, 8.0), slo_min_samples=5,
                    slo_breach_threshold=5.0)
    out = {"spawn_ok": True}
    try:
        _poll_until(lambda: set(router.replica_names()) == set(names),
                    30, "membership convergence to 3 replicas")

        def affine_prompt(target, length):
            # rejection-sample a prompt whose rendezvous choice is
            # `target` (deterministic from the run's RandomState)
            while True:
                p = rng.randint(0, 97, length).tolist()
                key = affinity_key(p, router.page_size,
                                   router.affinity_pages)
                if rendezvous_pick(key, names) == target:
                    return p

        def status(name):
            return router._status()["replicas"][name]

        # -- phase A: injected faults drain r2; the router rebalances.
        # One request per replica, concurrently: r0/r1 warm their
        # compiles while r2's request trips its fault schedule
        warm = [router.submit(affine_prompt(n, 12), max_new_tokens=8,
                              temperature=0.9) for n in names]
        for f in warm:
            assert f.result(timeout=240)["output_ids"]
        _poll_until(lambda: status("r2")["health"] == "draining", 10,
                    "router marking r2 draining")
        d2 = status("r2")["dispatched"]
        time.sleep(2 * router.health_poll_interval)
        futs = [router.submit(affine_prompt("r2", 12),
                              max_new_tokens=8) for _ in range(2)]
        for f in futs:
            assert f.result(timeout=240)["output_ids"]
        assert status("r2")["dispatched"] == d2, (
            "a draining replica received new admissions: "
            f"{status('r2')}")
        out["drain"] = {"rebalanced": router.n_rebalanced}
        assert router.n_rebalanced >= 1, router._status()

        # -- phase A2: POST /reset_health recovers r2 over HTTP
        from urllib.request import Request, urlopen
        base = infos["r2"]["healthz"].rsplit("/healthz", 1)[0]
        with urlopen(Request(base + "/reset_health", data=b"{}"),
                     timeout=10) as resp:
            assert resp.status == 200, resp.status
        _poll_until(lambda: status("r2")["health"] == "healthy", 10,
                    "r2 healthy after /reset_health")
        f = router.submit(affine_prompt("r2", 12), max_new_tokens=8)
        assert f.result(timeout=240)["output_ids"]
        assert status("r2")["dispatched"] > d2, (
            "recovered replica got no traffic back: "
            f"{status('r2')}")

        # -- phase B: SIGKILL r0 mid-decode — zero lost requests,
        # token-identical failover, breaker opens
        prompts = [affine_prompt("r0", 16) for _ in range(4)]
        futs = [router.submit(p, max_new_tokens=32, temperature=0.9)
                for p in prompts]
        _poll_until(lambda: status("r0")["inflight"] > 0, 60,
                    "r0 taking traffic before the kill")
        os.kill(procs["r0"].pid, signal.SIGKILL)
        procs["r0"].wait(timeout=30)
        # respawn starts NOW, overlapped with the zero-loss and
        # token-identity checks below (both take seconds — exactly the
        # boot window)
        respawned = {}

        def _respawn():
            respawned["proc"], respawned["info"] = spawn_replica(
                specs["r0"], timeout=180)

        respawn_t = threading.Thread(target=_respawn)
        respawn_t.start()
        # the breaker must trip well before the respawn can re-close
        # it (health polls hit connection-refused within ~3 intervals)
        _poll_until(lambda: status("r0")["breaker"] == "open", 15,
                    "r0 breaker opening after the kill")
        results = [f.result(timeout=240) for f in futs]
        assert all(r["output_ids"] for r in results), results
        flipped = [(p, r) for p, r in zip(prompts, results)
                   if r["failovers"] > 0]
        assert flipped, (
            "SIGKILL mid-decode caused no failover — the kill missed "
            f"the in-flight window: {[r['replica'] for r in results]}")
        for p, r in flipped[:2]:
            ref_out = ref.submit(p, max_new_tokens=32, temperature=0.9,
                                 nonce=r["request_id"])
            assert ref_out["output_ids"] == r["output_ids"], (
                "failover was not token-identical: "
                f"{ref_out['output_ids']} != {r['output_ids']}")
        out["kill"] = {"failovers": router.n_failovers,
                       "failover_requests": len(flipped)}

        # -- phase B2: r0 respawned (same name, new endpoints) — the
        # breaker must re-close through a half-open probe, and traffic
        # must return
        respawn_t.join(timeout=240)
        assert "proc" in respawned, "r0 respawn failed"
        procs["r0"], infos["r0"] = respawned["proc"], respawned["info"]
        _poll_until(lambda: status("r0")["breaker"] == "closed", 30,
                    "r0 breaker re-closing after respawn")
        assert status("r0")["breaker_opens"] >= 1
        d0 = status("r0")["dispatched"]
        f = router.submit(affine_prompt("r0", 16), max_new_tokens=8)
        assert f.result(timeout=240)["output_ids"]
        assert status("r0")["dispatched"] > d0, status("r0")
        assert router._aggregate_health() == "healthy", \
            router._status()

        # -- phase C: router-side fault sites replay from the seed
        faults.enable(seed=seed)
        faults.inject("router.dispatch", nth=(1,), times=1)
        futs = [router.submit(affine_prompt("r1", 12),
                              max_new_tokens=8) for _ in range(2)]
        for f in futs:
            assert f.result(timeout=240)["output_ids"]
        assert ("router.dispatch", 1) in faults.injected_log(), \
            faults.injected_log()
        _assert_schedule_matches(faults, ("router.dispatch",))
        faults.reset()
        out["router_faults"] = {"injected": 1}

        # -- phase D: fleet observability. /fleetz must aggregate all
        # three replicas with per-replica data; a deadline-miss storm
        # against the "gold" SLO class must move its burn-rate gauges
        # on /sloz and latch the breach (cleared by /reset_health)
        from urllib.request import Request, urlopen

        from paddle_tpu.observability.server import DebugServer
        from paddle_tpu.reliability.retry import DeadlineExceeded
        dbg = DebugServer(port=0).start()
        base = f"http://127.0.0.1:{dbg.port}"

        def get_json(path):
            with urlopen(base + path, timeout=10) as r:
                return json.loads(r.read())

        try:
            def fleetz_all_up():
                fz = next(iter(get_json("/fleetz")["fleets"].values()))
                reps = fz["replicas"]
                ok = all(n in reps and (reps[n].get("metrics") or {})
                         .get("up") for n in names) \
                    and fz["aggregates"]["tokens_generated"] > 0
                return fz if ok else None

            fz = _poll_until(fleetz_all_up, 15,
                             "/fleetz aggregating all 3 replicas")
            assert fz["aggregates"]["replicas_scraped"] == 3, fz
            sz = next(iter(get_json("/sloz")["slo"].values()))
            burn0 = sz["classes"].get("gold", {}).get(
                "windows", {}).get("short", {}).get("burn_rate", 0.0)
            assert burn0 == 0.0, f"gold budget burning before the " \
                f"storm: {sz}"
            storm = [router.submit(affine_prompt("r1", 8),
                                   max_new_tokens=4, slo="gold",
                                   deadline=0.001) for _ in range(8)]
            n_missed = 0
            for f in storm:
                try:
                    f.result(timeout=120)
                except DeadlineExceeded:
                    n_missed += 1
            assert n_missed == 8, f"storm deadlines not hopeless " \
                f"enough: {n_missed}/8 missed"
            sz = next(iter(get_json("/sloz")["slo"].values()))
            gold = sz["classes"]["gold"]
            assert gold["windows"]["short"]["burn_rate"] > 5.0, gold
            assert gold["windows"]["long"]["burn_rate"] > 5.0, gold
            assert "gold" in sz["breached"], sz
            hz = get_json("/healthz")
            slo_comp = [v for k, v in hz.get("components", {}).items()
                        if k.endswith("_slo")]
            assert slo_comp == ["degraded"], hz
            # operator acknowledgment clears the latch over HTTP
            with urlopen(Request(base + "/reset_health", data=b"{}"),
                         timeout=10) as resp:
                assert resp.status == 200, resp.status
            sz = next(iter(get_json("/sloz")["slo"].values()))
            assert sz["breached"] == [], sz
            out["slo"] = {"missed": n_missed,
                          "burn_short": gold["windows"]["short"]
                          ["burn_rate"]}
        finally:
            dbg.stop()
    except AssertionError:
        # the failure report attaches the merged cross-process trace:
        # every span table in the fleet (router + replica /tracez +
        # any flight dumps under obs_dir) on one ts_wall-aligned
        # timeline — the "which process ate the latency / dropped the
        # request" question answered next to the replay command
        path, summary = _attach_fleet_trace(workdir, infos)
        if path is not None:
            print(f"merged cross-process trace attached: {path} "
                  f"({summary['spans']} spans from "
                  f"{summary['processes']} processes)",
                  file=sys.stderr, flush=True)
        raise
    finally:
        faults.reset()
        tracing.disable()
        router.close()
        ref.engine.close()
        for p in procs.values():
            if p.poll() is None:
                p.kill()
                p.wait(timeout=30)
        store.close()
    return out


def disagg_soak(seed: int, workdir: str) -> dict:
    """Scenario 5c (rides ``--fleet``, ISSUE 18): the disaggregated
    prefill/decode fleet under migration-path chaos. One SPAWNED
    prefill replica (real HTTP /kv_pages) feeds two in-process decode
    replicas over int8 KV-page migration; asserts: the happy path is
    token-identical to a unified reference; a seeded router.migrate
    fault replays from the seed and falls back to local recompute
    (token-identical, request never lost); a page corrupted in flight
    is REJECTED by digest verification and recomputed locally
    (token-identical); SIGKILLing the prefill replica mid-migration
    degrades the same way; and the decode pools leak zero pages
    through all of it."""
    from paddle_tpu.inference import kv_transfer as kvt
    from paddle_tpu.reliability import faults
    from paddle_tpu.serving import (HTTPReplica, LocalReplica, Router,
                                    make_engine_from_spec,
                                    spawn_replica)

    rng = np.random.RandomState(seed + 1)
    faults.reset()
    model = {"platform": "cpu",   # a CPU-only gate by design
             "vocab": 97, "layers": 2, "hidden": 64, "heads": 4,
             "max_pos": 96, "model_seed": 0}
    engine_kw = {"page_size": 4, "num_pages": 96, "max_seqs": 4,
                 "prefill_chunk": 32, "seed": 0,
                 "kv_dtype": "int8"}
    spec = dict(model, name="pre0", role="prefill",
                engine=dict(engine_kw))
    proc, info = spawn_replica(spec, timeout=180)
    from paddle_tpu.core import compile_cache
    compile_cache.enable()
    dec = [make_engine_from_spec(dict(model, engine=dict(engine_kw)))
           for _ in range(2)]
    ref = make_engine_from_spec(dict(model, engine=dict(engine_kw)))
    prefill_client = HTTPReplica(info["generate"], info["healthz"],
                                 metrics_url=info.get("metrics"))

    class _TamperedPrefill:
        """Client wrapper that sabotages export_pages: 'corrupt'
        flips one KV byte in flight (digest verification must catch
        it); 'kill' SIGKILLs the prefill process first (the transfer
        must degrade to ReplicaUnavailable → local recompute)."""

        def __init__(self, inner):
            self.inner = inner
            self.mode = None

        def __getattr__(self, name):
            return getattr(self.inner, name)

        def export_pages(self, digests, trace_context=None):
            if self.mode == "kill":
                os.kill(proc.pid, signal.SIGKILL)
                proc.wait(timeout=30)
            payload = self.inner.export_pages(
                digests, trace_context=trace_context)
            if self.mode == "corrupt" and payload["pages"]:
                rec = payload["pages"][1]
                k = bytearray(kvt._unb64(rec["k"]))
                k[0] ^= 0x40
                rec["k"] = kvt._b64(bytes(k))
            return payload

    tampered = _TamperedPrefill(prefill_client)
    router = Router(page_size=4, disagg_threshold_tokens=8,
                    failover_budget=2, health_poll_interval=0.25)
    router.attach("pre0", tampered, role="prefill")
    router.attach("dec0", LocalReplica(dec[0]), role="decode")
    router.attach("dec1", LocalReplica(dec[1]), role="decode")
    out = {}

    def prompt_of(n=24):
        return rng.randint(0, 97, n).tolist()

    def check_identity(p, r, temperature=0.0):
        want = ref.submit(p, max_new_tokens=16,
                          temperature=temperature,
                          nonce=r["request_id"]).result(timeout=240)
        assert want["output_ids"] == r["output_ids"], (
            "disagg stream diverged from the unified reference: "
            f"{want['output_ids']} != {r['output_ids']}")

    try:
        # -- phase A: happy-path migration, greedy AND seeded
        p = prompt_of()
        r = router.submit(p, max_new_tokens=16).result(timeout=240)
        assert r["replica"].startswith("dec"), r
        assert r.get("migrated_pages", 0) > 0, (
            "long uncached prompt did not migrate: "
            f"{router._status()['migrations']}")
        check_identity(p, r)
        p = prompt_of()
        r = router.submit(p, max_new_tokens=16,
                          temperature=0.9).result(timeout=240)
        assert r.get("migrated_pages", 0) > 0, r
        check_identity(p, r, temperature=0.9)
        assert router.n_migrations == 2, router._status()
        out["happy"] = dict(router._status()["migrations"])

        # -- phase B: seeded router.migrate fault — fallback to local
        # recompute, seed-replayable schedule, request never lost
        faults.enable(seed=seed)
        faults.inject("router.migrate", nth=(1,), times=1)
        p = prompt_of()
        r = router.submit(p, max_new_tokens=16).result(timeout=240)
        assert "migrate_s" not in r, r
        check_identity(p, r)
        assert ("router.migrate", 1) in faults.injected_log(), \
            faults.injected_log()
        _assert_schedule_matches(faults, ("router.migrate",))
        faults.reset()
        assert router.n_migrate_failed == 1, router._status()
        out["fault_fallback"] = {"failed": router.n_migrate_failed}

        # -- phase C: one page corrupted in flight — digest
        # verification rejects it, the decode replica recomputes the
        # gap locally, the stream stays identical, nothing leaks
        tampered.mode = "corrupt"
        p = prompt_of()
        r = router.submit(p, max_new_tokens=16).result(timeout=240)
        tampered.mode = None
        assert r.get("migrated_pages", 5) < 5, (
            "corrupt page was not rejected: "
            f"{router._status()['migrations']}")
        assert router.n_pages_rejected >= 1, router._status()
        check_identity(p, r)
        out["corruption"] = {
            "rejected": router.n_pages_rejected,
            "installed": r.get("migrated_pages")}

        # -- phase D: prefill replica SIGKILLed mid-migration — the
        # pull fails, the request falls back and completes locally
        tampered.mode = "kill"
        p = prompt_of()
        r = router.submit(p, max_new_tokens=16).result(timeout=240)
        assert "migrate_s" not in r, r
        check_identity(p, r)
        assert router.n_migrate_failed == 2, router._status()
        out["kill"] = {"failed": router.n_migrate_failed}

        # -- leak audit: idle decode pools must account for every
        # page (free + shared residents + the scratch page)
        for eng in dec:
            free = len(eng._free_pages)
            shared = eng._cache.shared_page_count
            assert free + shared + 1 == eng.num_pages, (
                f"page leak: free={free} shared={shared} "
                f"of {eng.num_pages}")
        out["pages_leaked"] = 0
    finally:
        faults.reset()
        router.close()
        for eng in dec + [ref]:
            eng.close()
        if proc.poll() is None:
            proc.kill()
            proc.wait(timeout=30)
    for eng in dec:
        assert len(eng._free_pages) == eng.num_pages - 1, (
            "decode pool did not return to full size at close")
    return out


def drift_soak(seed: int, workdir: str) -> dict:
    """Scenario 5d (rides ``--fleet``, ISSUE 19): the stream-integrity
    auditor under a drift storm. Asserts the acceptance invariants:
    a fault-free shadow storm (audit_shadow_rate=1.0) verifies every
    stream with ZERO divergences; a seeded ``audit.flip`` — one token
    XOR-flipped BEFORE the digest chain extends over it, so the
    corrupted stream is self-consistent and only chain-vs-chain
    comparison can see it — is caught by the shadow re-execution at
    the EXACT divergent position, with a one-shot stream_divergence
    flight dump carrying both chain heads and both knob fingerprints;
    the same flip under an engine device-retry is caught by the
    retry's prefix check (``kind="failover"``, exact position); the
    fault schedule replays from the seed; and a final clean storm
    records zero NEW divergences (a tripped auditor must not keep
    crying wolf)."""
    from paddle_tpu.core import flags as flags_mod
    from paddle_tpu.observability import audit, flight
    from paddle_tpu.reliability import faults
    from paddle_tpu.serving import (LocalReplica, Router,
                                    make_engine_from_spec)

    rng = np.random.RandomState(seed + 2)
    faults.reset()
    audit.reset()
    audit.enable()
    old_rate = flags_mod.get_flag("audit_shadow_rate")
    flags_mod.set_flags({"audit_shadow_rate": 1.0})
    fdir = os.path.join(workdir, "drift_flight")
    rec = flight.FlightRecorder(fdir)
    rec.install()
    model = {"platform": "cpu",   # a CPU-only gate by design
             "vocab": 97, "layers": 2, "hidden": 64, "heads": 4,
             "max_pos": 96, "model_seed": 0}
    engine_kw = {"max_seqs": 4, "page_size": 4, "num_pages": 64,
                 "prefill_chunk": 32, "seed": 0,
                 "device_retry_budget": 2}
    engs = [make_engine_from_spec(dict(model, engine=dict(engine_kw)))
            for _ in range(2)]
    router = Router({"a": LocalReplica(engs[0]),
                     "b": LocalReplica(engs[1])},
                    failover_budget=2, health_poll_interval=0.25)
    out = {}

    def counts():
        return audit.instance().counts()

    try:
        # -- phase A: fault-free shadow storm — every served stream is
        # re-executed off-path and chain-diffed; zero divergences
        futs = [router.submit(rng.randint(0, 97, 12).tolist(),
                              max_new_tokens=8, temperature=0.9)
                for _ in range(6)]
        for f in futs:
            assert f.result(timeout=240)["stream_digest"]
        _poll_until(lambda: counts()["verified"] >= 6, 120,
                    "clean-storm shadows verifying")
        assert counts()["diverged"] == 0, audit.driftz_payload()
        out["clean"] = dict(counts())

        # -- phase B: seeded audit.flip — flip the 4th delivered
        # token; the served stream is self-consistent (its digest
        # matches its tokens) so only the shadow's chain-vs-chain
        # diff can catch it, at EXACTLY position 3 (0-based)
        faults.enable(seed=seed)
        faults.inject("audit.flip", nth=(4,), times=1)
        r = router.submit(rng.randint(0, 97, 12).tolist(),
                          max_new_tokens=8,
                          temperature=0.9).result(timeout=240)
        assert r["stream_digest"]          # self-consistent: served
        _poll_until(lambda: counts()["diverged"] >= 1, 120,
                    "shadow catching the flipped token")
        div = audit.driftz_payload()["scopes"]["router"][
            "last_divergence"]
        assert div["kind"] == "shadow", div
        assert div["position"] == 3, (
            f"divergence not at the flipped token: {div}")
        assert div["chain_ours"] != div["chain_theirs"], div
        assert div["knobs_ours"] is not None, div
        assert ("audit.flip", 4) in faults.injected_log(), \
            faults.injected_log()
        _assert_schedule_matches(faults, ("audit.flip",))
        dumps = [f for f in os.listdir(fdir)
                 if "stream_divergence" in f]
        assert len(dumps) == 1, (
            f"expected exactly one one-shot divergence dump: {dumps}")
        rows = [json.loads(line)
                for line in open(os.path.join(fdir, dumps[0]))]
        extra = [x for x in rows if x.get("kind") == "extra"]
        assert extra and extra[0]["divergence"]["position"] == 3, rows
        out["flip"] = {"position": div["position"],
                       "dump": dumps[0]}

        # -- phase C: the flip under an engine device-retry — the
        # retry re-admits with the same nonce and must re-emit the
        # exact prefix the failed incarnation delivered; the flipped
        # token #2 makes the prefixes differ at position 1
        faults.reset()
        faults.enable(seed=seed)
        faults.inject("audit.flip", nth=(2,), times=1)
        eng = engs[0]
        real = eng._decode_fn
        state = {"n": 0}

        def flaky(*a, **kw):
            state["n"] += 1
            if state["n"] == 5:        # die after ~4 clean ticks
                raise RuntimeError("transient PJRT failure")
            return real(*a, **kw)

        eng._decode_fn = flaky
        try:
            r = eng.submit([5, 6, 7, 8], max_new_tokens=8,
                           temperature=0.8).result(timeout=240)
        finally:
            eng._decode_fn = real
        assert r["output_ids"] and r["stream_digest"]
        sc = audit.driftz_payload()["scopes"]
        escope = next((s for n, s in sc.items() if n != "router"
                       and s["by_kind"]["failover"]), None)
        assert escope is not None, sc
        ediv = escope["last_divergence"]
        assert ediv["kind"] == "failover" and ediv["position"] == 1, \
            ediv
        _assert_schedule_matches(faults, ("audit.flip",))
        faults.reset()
        out["device_retry"] = {"position": ediv["position"]}

        # -- phase D: clean storm after the incident — divergence
        # counts must NOT move (the auditor detects drift, it does
        # not manufacture it)
        before = counts()["diverged"]
        futs = [router.submit(rng.randint(0, 97, 12).tolist(),
                              max_new_tokens=8, temperature=0.9)
                for _ in range(4)]
        for f in futs:
            assert f.result(timeout=240)["stream_digest"]
        _poll_until(
            lambda: counts()["verified"] >= out["clean"]["verified"]
            + 4, 120, "post-incident clean storm verifying")
        assert counts()["diverged"] == before, audit.driftz_payload()
        out["post_clean"] = dict(counts())
    finally:
        faults.reset()
        flags_mod.set_flags({"audit_shadow_rate": old_rate})
        rec.uninstall()
        router.close()
        for eng in engs:
            eng.close()
    return out


def autoscale_soak(seed: int, workdir: str) -> dict:
    """Scenario 5b (``--autoscale``, ISSUE 13): the SLO-driven
    autoscaler over a LIVE subprocess fleet. Asserts the acceptance
    invariants: a deadline-miss storm trips the gold class's burn
    windows and triggers a scale-out whose FIRST spawn attempt dies on
    the seeded ``autoscale.spawn`` fault (the retry must absorb it and
    never double-count capacity; the replica counts only after READY +
    a successful health probe); a SIGKILL of the autoscaled replica
    mid-decode loses ZERO requests (nonce-pinned token-identical
    failover, checked against a reference engine) and is respawned as
    a REPLACEMENT, not a scale-out; a seeded ``autoscale.drain`` fault
    expires the scale-in drain deadline with stragglers in flight,
    which must complete token-identically on a sibling; the terminated
    replica is withdrawn from TCPStore membership immediately (no
    stale-record re-attach); and both autoscale fault sites replay
    from the seed. Failures attach the merged cross-process trace
    next to the fault seed + replay command, like every fleet phase."""
    from paddle_tpu.distributed.tcp_store import (TCPMembership,
                                                  TCPStoreClient,
                                                  TCPStoreServer)
    from paddle_tpu.observability import tracing
    from paddle_tpu.reliability import faults
    from paddle_tpu.reliability.retry import DeadlineExceeded
    from paddle_tpu.serving import (Autoscaler, HTTPReplica,
                                    LocalReplica, Router, SLOClass,
                                    make_engine_from_spec,
                                    make_subprocess_spawner,
                                    spawn_replica)
    from paddle_tpu.serving.router import affinity_key, rendezvous_pick

    rng = np.random.RandomState(seed)
    faults.reset()
    tracing.enable()
    store = TCPStoreServer("127.0.0.1", 0)
    endpoint = f"127.0.0.1:{store.port}"
    obs_dir = os.path.join(workdir, "obs")
    model = {"platform": "cpu",   # a CPU-only gate by design
             "vocab": 97, "layers": 2, "hidden": 64, "heads": 4,
             "max_pos": 96, "model_seed": 0,
             "tracing": True, "obs_dir": obs_dir}
    engine_kw = {"device_retry_budget": 2, "max_pending": 64,
                 "seed": 0}
    # the seed replica (unmanaged — the autoscaler can only kill what
    # it spawned) boots first and warms the shared compile cache
    procs, infos = {}, {}
    spec0 = dict(model, name="r0", store=endpoint,
                 engine=dict(engine_kw))
    procs["r0"], infos["r0"] = spawn_replica(spec0, timeout=180)
    HTTPReplica(infos["r0"]["generate"],
                infos["r0"]["healthz"]).submit([1, 2, 3],
                                               max_new_tokens=2)
    # reference engine: same weights/seed/cache — replays any
    # failover'd stream nonce-pinned to pin token identity
    from paddle_tpu.core import compile_cache
    compile_cache.enable()
    ref = LocalReplica(make_engine_from_spec(dict(model,
                                                  engine=engine_kw)))
    ref.submit([1, 2, 3], max_new_tokens=1)

    router = Router(store_endpoint=endpoint, page_size=16,
                    affinity_pages=2, failover_budget=2,
                    health_poll_interval=0.2,
                    membership_stale_after=1.5,
                    breaker_fail_threshold=3, breaker_open_for=1.0,
                    slo_classes={"gold": SLOClass(
                        "gold", deadline_s=60.0, target=0.99)},
                    slo_windows=(2.0, 8.0), slo_min_samples=5,
                    slo_breach_threshold=5.0)
    auto_spec = dict(model, store=endpoint,
                     engine=dict(engine_kw))
    scaler = Autoscaler(
        router, make_subprocess_spawner(auto_spec, timeout=180),
        min_replicas=1, max_replicas=2, replica_slots=4,
        # scale-in disarmed until phase C flips low_water — the
        # phases need the fleet to HOLD at 2 through the SIGKILL
        low_water=-1.0, dwell_s=3.0,
        backoff_base_s=0.5, backoff_cap_s=8.0,
        drain_deadline_s=30.0, spawn_backoff_s=0.2,
        ready_timeout_s=180.0, name_prefix="auto")
    out = {}
    client = TCPStoreClient(endpoint)

    def affine_prompt(target, names, length):
        while True:
            p = rng.randint(0, 97, length).tolist()
            key = affinity_key(p, router.page_size,
                               router.affinity_pages)
            if rendezvous_pick(key, names) == target:
                return p

    try:
        _poll_until(lambda: router.replica_names() == ["r0"], 30,
                    "r0 membership convergence")
        scaler.start()
        faults.enable(seed=seed)
        # the FIRST spawn attempt of the storm's scale-out must die
        # and be retried without ghost capacity
        faults.inject("autoscale.spawn", nth=(1,))

        # -- phase A: gold deadline-miss storm trips both burn
        # windows → scale-out (1 → 2), spawn fault absorbed
        storm = [router.submit(rng.randint(0, 97, 8).tolist(),
                               max_new_tokens=4, slo="gold",
                               deadline=0.001) for _ in range(8)]
        n_missed = 0
        for f in storm:
            try:
                f.result(timeout=120)
            except DeadlineExceeded:
                n_missed += 1
        assert n_missed == 8, (
            f"storm deadlines not hopeless enough: {n_missed}/8")
        _poll_until(lambda: scaler.n_scale_out >= 1, 240,
                    "burn-tripped scale-out")
        _poll_until(
            lambda: router.fleet_load(4)["ready"] == 2, 240,
            "spawned replica READY + healthy and counted")
        d_out = [d for d in scaler.decisions()
                 if d["action"] == "scale_out"][0]
        assert d_out["reason"].startswith("slo_burn:gold"), d_out
        assert d_out["attempts"] == 2, (
            f"autoscale.spawn fault was not retried: {d_out}")
        load = router.fleet_load(4)
        assert load["attached"] == 2 and load["warming"] == 0, (
            f"failed spawn attempt left ghost capacity: {load}")
        auto1 = d_out["replica"]
        h1 = scaler._managed[auto1].handle
        infos[auto1] = dict(h1.info)
        out["scale_out"] = {"replica": auto1,
                            "attempts": d_out["attempts"],
                            "missed": n_missed}

        # -- phase B: SIGKILL the autoscaled replica mid-decode —
        # zero lost requests (token-identical failover), respawned as
        # a REPLACEMENT (not a scale-out)
        names = ("r0", auto1)
        prompts = [affine_prompt(auto1, names, 16) for _ in range(4)]
        futs = [router.submit(p, max_new_tokens=32, temperature=0.9)
                for p in prompts]
        _poll_until(lambda: (router.inflight_of(auto1) or 0) > 0, 60,
                    "autoscaled replica taking traffic")
        os.kill(h1.proc.pid, signal.SIGKILL)
        h1.proc.wait(timeout=30)
        results = [f.result(timeout=240) for f in futs]
        assert all(r["output_ids"] for r in results), results
        flipped = [(p, r) for p, r in zip(prompts, results)
                   if r["failovers"] > 0]
        assert flipped, (
            "SIGKILL mid-decode caused no failover — the kill missed "
            f"the in-flight window: {[r['replica'] for r in results]}")
        for p, r in flipped[:2]:
            ref_out = ref.submit(p, max_new_tokens=32,
                                 temperature=0.9,
                                 nonce=r["request_id"])
            assert ref_out["output_ids"] == r["output_ids"], (
                "failover was not token-identical: "
                f"{ref_out['output_ids']} != {r['output_ids']}")
        _poll_until(lambda: scaler.n_replaced >= 1, 240,
                    "replacement spawn after the SIGKILL")
        _poll_until(
            lambda: router.fleet_load(4)["ready"] == 2, 240,
            "replacement READY + healthy")
        assert scaler.n_scale_out == 1, (
            "a SIGKILL respawn was counted as a scale-out: "
            f"{scaler.decisions()}")
        d_rep = [d for d in scaler.decisions()
                 if d["action"] == "replace"][-1]
        auto2 = d_rep["replica"]
        h2 = scaler._managed[auto2].handle
        infos[auto2] = dict(h2.info)
        _poll_until(
            lambda: auto1 not in TCPMembership.list_members(client),
            15, "dead replica withdrawn from the roster")
        out["kill"] = {"failovers": len(flipped),
                       "replacement": auto2}

        # -- phase C: scale-in under the seeded drain fault — the
        # drain deadline expires with stragglers in flight, the kill
        # proceeds, and the stragglers complete token-identically on
        # the sibling. Zero lost requests across the scale-in.
        faults.inject("autoscale.drain", nth=(1,))
        names = ("r0", auto2)
        c_prompts = [affine_prompt(auto2, names, 16)
                     for _ in range(6)]
        c_futs = [router.submit(p, max_new_tokens=64,
                                temperature=0.9) for p in c_prompts]
        _poll_until(lambda: (router.inflight_of(auto2) or 0) > 0, 60,
                    "victim holding in-flight work")
        scaler.low_water = 0.8      # arm the scale-in trigger
        _poll_until(lambda: scaler.n_scale_in >= 1, 120,
                    "fault-forced scale-in")
        c_results = [f.result(timeout=240) for f in c_futs]
        assert all(r["output_ids"] for r in c_results), c_results
        d_in = [d for d in scaler.decisions()
                if d["action"] == "scale_in"][-1]
        assert d_in["replica"] == auto2, d_in
        assert d_in["stragglers"] >= 1, (
            f"the drain fault should have expired the deadline with "
            f"stragglers in flight: {d_in}")
        moved = [(p, r) for p, r in zip(c_prompts, c_results)
                 if r["replica"] != auto2]
        assert moved, (
            "no straggler finished on a sibling — the drain kill "
            f"lost its in-flight work? {c_results}")
        for p, r in moved[:2]:
            ref_out = ref.submit(p, max_new_tokens=64,
                                 temperature=0.9,
                                 nonce=r["request_id"])
            assert ref_out["output_ids"] == r["output_ids"], (
                "straggler failover was not token-identical: "
                f"{ref_out['output_ids']} != {r['output_ids']}")
        _poll_until(
            lambda: router.fleet_load(4)["ready"] == 1, 60,
            "fleet back at min_replicas after the scale-in")
        _poll_until(
            lambda: set(TCPMembership.list_members(client)) == {"r0"},
            15, "scaled-in replica withdrawn from the roster")
        out["scale_in"] = {"stragglers": d_in["stragglers"],
                           "drain_s": d_in["drain_s"],
                           "moved": len(moved)}

        # -- determinism: both autoscale sites replay from the seed
        _assert_schedule_matches(
            faults, ("autoscale.spawn", "autoscale.drain"))
        out["decisions"] = len(scaler.decisions())
    except AssertionError:
        path, summary = _attach_fleet_trace(workdir, infos)
        if path is not None:
            print(f"merged cross-process trace attached: {path} "
                  f"({summary['spans']} spans from "
                  f"{summary['processes']} processes)",
                  file=sys.stderr, flush=True)
        raise
    finally:
        faults.reset()
        tracing.disable()
        scaler.close(terminate_managed=True)
        router.close()
        ref.engine.close()
        for p in procs.values():
            if p.poll() is None:
                p.kill()
                p.wait(timeout=30)
        store.close()
    return out


def overload_soak(seed: int, workdir: str) -> dict:
    """Scenario 5c (``--overload``, ISSUE 20): the brownout controller
    under a seeded burst storm. Two in-process replicas behind a
    Router with an :class:`OverloadController`; three rounds of
    deadline-doomed bronze bursts (plus protected gold) trip the
    bronze burn windows and walk the ladder up; the storm draining
    walks it back to normal within its dwell bounds. Asserts: every
    future resolves TYPED, gold loses zero requests, the ladder moves
    one level per transition, the seeded ``overload.estimate``
    distortion surfaces as hopeless-shed verdicts (never a hang), the
    seeded ``overload.step`` escalation is walked back by hysteresis,
    and both sites replay from the seed."""
    from paddle_tpu.inference.llm import (AdmissionShed, LLMEngine,
                                          OverloadShed)
    from paddle_tpu.reliability import faults
    from paddle_tpu.reliability.retry import DeadlineExceeded
    from paddle_tpu.serving import (AIMDLimiter, BrownoutLadder,
                                    LocalReplica, OverloadController,
                                    Router, SLOClass,
                                    ServiceTimeEstimator)

    rng = np.random.RandomState(seed)
    faults.reset()

    def build_engine():
        return LLMEngine(_tiny_gpt(), max_seqs=4, page_size=4,
                         num_pages=96, prefill_chunk=16,
                         max_pending=64, admit_timeout=60.0, seed=0)

    engines = [build_engine(), build_engine()]
    for e in engines:           # shared in-process compile warmup
        e.generate([[1, 2, 3]], max_new_tokens=2)
    # injected rate source: deterministic predictions (the perf-
    # registry path is the bench's job; the soak pins CONTROL flow)
    ctrl = OverloadController(
        estimator=ServiceTimeEstimator(source=lambda: (4000.0, 800.0)),
        limiter=AIMDLimiter(floor=1, ceiling=8),
        ladder=BrownoutLadder(up_dwell_s=0.2, down_dwell_s=0.3,
                              backoff_base_s=0.2, backoff_cap_s=1.0),
        bronze_max_new_tokens=8)
    router = Router({"r0": LocalReplica(engines[0]),
                     "r1": LocalReplica(engines[1])},
                    health_poll_interval=0.1, scrape_metrics=False,
                    slo_classes={
                        "gold": SLOClass("gold", deadline_s=60.0,
                                         target=0.99),
                        "bronze": SLOClass("bronze", deadline_s=0.08,
                                           target=0.99)},
                    slo_windows=(1.0, 4.0), slo_min_samples=4,
                    slo_breach_threshold=5.0, overload=ctrl)
    outcomes = {"ok": 0, "deadline": 0, "shed": 0, "error": 0}
    gold_lost, max_level = [], [0]
    stop_watch = threading.Event()

    def watch_level():
        while not stop_watch.is_set():
            max_level[0] = max(max_level[0], ctrl.level)
            time.sleep(0.02)

    watcher = threading.Thread(target=watch_level, daemon=True)
    watcher.start()

    def tally(futs):
        done, not_done = fut_wait([f for _s, f in futs],
                                  timeout=FUTURE_TIMEOUT)
        assert not not_done, (
            f"{len(not_done)} futures never resolved — the overload "
            f"controller hung the router")
        for slo, f in futs:
            exc = f.exception()
            if exc is None:
                outcomes["ok"] += 1
            elif isinstance(exc, DeadlineExceeded):
                outcomes["deadline"] += 1
                if slo == "gold":
                    gold_lost.append(("deadline", str(exc)))
            elif isinstance(exc, AdmissionShed):
                outcomes["shed"] += 1
                if slo == "gold":
                    gold_lost.append(("shed", str(exc)))
            else:
                outcomes["error"] += 1
                gold_lost.append((type(exc).__name__, str(exc)))

    try:
        faults.enable(seed=seed)
        # 2nd + 7th predictions distort 1000× (→ hopeless sheds); the
        # overload.step escalation is armed LATER, once the ladder is
        # back at normal — forced at max level it would clamp to a
        # no-op and the walk-back assertion would test nothing
        faults.inject("overload.estimate", nth=(2, 7))

        # -- 3× burst storm: bronze is deadline-doomed (0.08 s for a
        # 24-token decode), gold is generously budgeted and PROTECTED
        for _round in range(3):
            futs = [("bronze",
                     router.submit(rng.randint(0, 97, 12).tolist(),
                                   max_new_tokens=24, slo="bronze"))
                    for _ in range(12)]
            futs += [("gold",
                      router.submit(rng.randint(0, 97, 8).tolist(),
                                    max_new_tokens=4, slo="gold"))
                     for _ in range(4)]
            tally(futs)
            time.sleep(0.3)     # let ticks see the burn windows
        _poll_until(lambda: ctrl.level >= 1 or max_level[0] >= 1, 30,
                    "ladder engaging under the bronze burn signal")

        # -- quiet: bronze samples age out of the (1 s, 4 s) windows,
        # the ladder walks back down one dwell-bounded level at a time
        _poll_until(lambda: ctrl.level == 0, 60,
                    "ladder walking back to normal after the storm")

        # -- spurious escalation: force the ladder UP from normal on a
        # seeded tick (2 calls out — ticks ride the 0.1 s poll, so the
        # fault lands while the fleet is demonstrably calm) and assert
        # the hysteresis walks it back without any real burn signal
        faults.inject("overload.step",
                      nth=(faults.call_count("overload.step") + 2,))
        _poll_until(
            lambda: any(t["reason"].startswith("fault_injected")
                        for t in ctrl.ladder.transitions()), 30,
            "seeded overload.step escalation landing")
        _poll_until(lambda: ctrl.level == 0, 60,
                    "hysteresis walking back the spurious escalation")
        stop_watch.set()
        watcher.join(timeout=5)

        assert outcomes["error"] == 0, (
            f"untyped resolutions under overload chaos: {outcomes}, "
            f"first: {gold_lost[:3]}")
        assert not gold_lost, (
            f"gold lost {len(gold_lost)} request(s) — the protected "
            f"class must never be shed or missed: {gold_lost[:3]}")
        assert outcomes["shed"] + outcomes["deadline"] > 0, (
            f"the storm was not a storm: {outcomes}")
        shed_counts = dict(ctrl.n_shed)
        assert shed_counts.get("hopeless", 0) >= 1, (
            "the seeded overload.estimate distortion never surfaced "
            f"as a hopeless shed: {shed_counts}")
        trans = ctrl.ladder.transitions()
        assert max_level[0] >= 1 and any(
            t["to"] > t["from"] for t in trans), (
            f"the ladder never engaged: max={max_level[0]}, {trans}")
        assert all(abs(t["to"] - t["from"]) == 1
                   for t in trans), (
            f"a transition jumped more than one level: {trans}")
        assert any(t["reason"].startswith("fault_injected")
                   for t in trans), (
            "the seeded overload.step escalation never landed: "
            f"{trans}")
        assert len(trans) <= 24, (
            f"ladder flapped {len(trans)} transitions — hysteresis "
            f"is not damping: {trans}")
        assert ctrl.level == 0, f"ladder stuck at {ctrl.level}"

        # -- determinism: both overload sites replay from the seed
        _assert_schedule_matches(
            faults, ("overload.estimate", "overload.step"))
        return {"outcomes": outcomes, "max_level": max_level[0],
                "transitions": len(trans), "shed": shed_counts,
                "limits": ctrl.limiter.state()}
    finally:
        stop_watch.set()
        faults.reset()
        router.close()
        for e in engines:
            e.close()


TRAIN_STEPS = 16          # 2 epochs × 8 steps (32 samples / batch 4)
TRAIN_EPOCH_STEPS = TRAIN_STEPS // 2
TRAIN_CKPT_FREQ = 5


def train_soak(seed: int, workdir: str) -> dict:
    """Scenario 6: kill-anywhere / resume-exactly. For steps_per_loop
    ∈ {1, 4}: an uninterrupted baseline, then seeded kills (SIGKILL in
    the STEP/SNAPSHOT/COMMIT/GC windows, SIGTERM for the graceful
    emergency-flush path), then relaunch-to-completion — the combined
    loss stream must be bit-identical to the baseline at every step,
    including steps re-run after resuming from an older checkpoint.
    Plus in-process: corrupt-checkpoint quarantine + fallback, seeded
    replay of the ckpt.snapshot/ckpt.async_commit fault sites, and the
    async-save stall bound (snapshot time, not commit time)."""
    rng = np.random.RandomState(seed)
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)

    def launch(run_dir, k):
        os.makedirs(run_dir, exist_ok=True)
        return subprocess.Popen(
            [sys.executable, os.path.abspath(__file__), "--train-worker",
             run_dir, str(k), str(TRAIN_CKPT_FREQ)],
            env=env, cwd=REPO, stdout=subprocess.PIPE, text=True)

    def read_losses(run_dir):
        out = {}
        path = os.path.join(run_dir, "losses.txt")
        if os.path.exists(path):
            for ln in open(path):
                s, h = ln.split()
                out.setdefault(int(s), []).append(h)
        return out

    def run_complete(run_dir, k):
        p = launch(run_dir, k)
        out_text, _ = p.communicate(timeout=300)
        assert p.returncode == 0, out_text[-800:]
        assert "DONE" in out_text, out_text[-400:]

    def run_and_kill(run_dir, k, kind, occurrence, jitter):
        """Kill the worker at the chosen marker occurrence (seeded
        jitter inside the window). kind="TERM" sends SIGTERM at a STEP
        marker instead — the graceful-preemption path — and asserts
        the deadline-budgeted flush exits RESTART_EXIT_CODE. Returns
        the window the worker died in, or None if it finished first."""
        from paddle_tpu.distributed.elastic import RESTART_EXIT_CODE
        p = launch(run_dir, k)
        target = "STEP" if kind == "TERM" else kind
        seen = 0
        died_in = None
        for line in p.stdout:
            parts = line.split()
            if not parts:
                continue
            if parts[0] == "DONE":
                break
            if parts[0] == target:
                seen += 1
                if seen >= occurrence:
                    time.sleep(jitter)
                    if kind == "TERM":
                        p.send_signal(signal.SIGTERM)
                    else:
                        p.kill()
                    died_in = kind
                    break
        p.wait(timeout=180)
        if died_in == "TERM":
            assert p.returncode == RESTART_EXIT_CODE, (
                f"SIGTERM mid-training exited {p.returncode}, not "
                f"{RESTART_EXIT_CODE} — the PreemptionGuard emergency "
                f"flush path is broken")
        return died_in

    # pre-draw every seeded choice, then run the two independent
    # steps_per_loop lanes CONCURRENTLY (each is mostly subprocess
    # startup + pipe waits): determinism stays a pure function of the
    # seed while the wall clock halves toward the CI budget
    kinds = ["SNAPSHOT", "COMMIT", "GC", "TERM", "STEP"]
    order = [kinds[int(i)] for i in rng.permutation(len(kinds))]
    plans = []
    for ki, k in enumerate((1, 4)):
        lane = []
        for kind in order[2 * ki: 2 * ki + 2]:
            occurrence = int(rng.randint(2, 14)
                             if kind in ("STEP", "TERM")
                             else rng.randint(1, 3))
            lane.append((kind, occurrence,
                         float(rng.uniform(0.0, 0.02))))
        plans.append((k, lane))
    out = {"kills": []}

    # both uninterrupted baselines ride ONE subprocess (one jax
    # import, shared warm caches) before the kill lanes fan out
    base1 = os.path.join(workdir, "train_base_k1")
    base4 = os.path.join(workdir, "train_base_k4")
    os.makedirs(base1, exist_ok=True)
    os.makedirs(base4, exist_ok=True)
    p = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--train-baseline",
         base1, base4, str(TRAIN_CKPT_FREQ)],
        env=env, cwd=REPO, stdout=subprocess.PIPE, text=True,
        timeout=300)
    assert p.returncode == 0 and p.stdout.count("DONE") == 2, (
        f"baseline run failed rc={p.returncode}: {p.stdout[-800:]}")

    def lane_run(k, lane):
        baseline = read_losses(os.path.join(workdir,
                                            f"train_base_k{k}"))
        assert sorted(baseline) == list(range(TRAIN_STEPS)), (
            f"k={k} baseline incomplete: {sorted(baseline)}")
        ref = {s: v[0] for s, v in baseline.items()}

        run_dir = os.path.join(workdir, f"train_kill_k{k}")
        kills = []
        for kind, occurrence, jitter in lane:
            died_in = run_and_kill(run_dir, k, kind, occurrence, jitter)
            kills.append({"k": k, "kind": kind,
                          "occurrence": occurrence,
                          "landed": bool(died_in)})
        run_complete(run_dir, k)  # final incarnation finishes the range
        got = read_losses(run_dir)
        assert sorted(got) == list(range(TRAIN_STEPS)), (
            f"k={k}: killed/resumed run lost steps: {sorted(got)}")
        for s in range(TRAIN_STEPS):
            for h in got[s]:
                assert h == ref[s], (
                    f"k={k} step {s}: resumed loss {h} != baseline "
                    f"{ref[s]} — resume is not bit-identical")
        return kills, sum(len(v) for v in got.values())

    lane_res: dict = {}

    def lane_thread(k, lane):
        try:
            lane_res[k] = lane_run(k, lane)
        except BaseException as e:  # noqa: BLE001 — re-raised below
            lane_res[k] = e

    threads = [threading.Thread(target=lane_thread, args=(k, lane))
               for k, lane in plans]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    for k, _lane in plans:
        res = lane_res.get(k)
        if isinstance(res, BaseException):
            raise res
        kills, loss_lines = res
        out["kills"].extend(kills)
        out[f"k{k}"] = {"loss_lines": loss_lines}
    landed = sum(1 for kl in out["kills"] if kl["landed"])
    assert landed >= 2, (
        f"only {landed}/4 seeded kills landed inside the run — the "
        f"soak under-exercised the kill windows: {out['kills']}")
    out.update(_train_soak_inprocess(seed, workdir))
    out["guard"] = _train_soak_guard(seed, workdir)
    return out


def _train_soak_guard(seed: int, workdir: str) -> dict:
    """Scenario 6b: the poisoned-stream numeric-guard gate. Any
    assertion failure prints the fault seed + replay command and
    attaches a flight-recorder dump (same contract as the fleet/train
    phases)."""
    import hashlib

    from paddle_tpu import Model, nn, optimizer as pt_opt, seed as pt_seed
    from paddle_tpu.io import TensorDataset, stack_batches
    from paddle_tpu.io.checkpoint import CheckpointManager
    from paddle_tpu.observability import flight
    from paddle_tpu.reliability import faults
    from paddle_tpu.reliability import guard as nguard

    rng = np.random.RandomState(seed)
    n_batches, batch = 16, 4
    batches = [(rng.randn(batch, 8).astype(np.float32),
                rng.randint(0, 4, (batch, 1)))
               for _ in range(n_batches)]

    def build(policy):
        pt_seed(0)
        net = nn.Sequential(nn.Linear(8, 16), nn.Tanh(),
                            nn.Linear(16, 4))
        m = Model(net)
        # constant-LR Adam, no dropout: the exactness scope of skip ≡
        # clean-minus (per-step keys / LR schedules would key on the
        # shifted step index)
        m.prepare(optimizer=pt_opt.Adam(learning_rate=1e-2,
                                        parameters=net),
                  loss=nn.CrossEntropyLoss(), numeric_guard=policy)
        return m

    def params_hex(m):
        m.sync_weights()
        h = hashlib.blake2b(digest_size=16)
        for name, v in sorted(m.network.state_dict().items()):
            h.update(name.encode())
            h.update(np.ascontiguousarray(np.asarray(v)).tobytes())
        return h.hexdigest()

    def run(m, k, skip_idx=()):
        kept = [b for i, b in enumerate(batches) if i not in skip_idx]
        if k == 1:
            for x, y in kept:
                m.train_batch([x], [y])
        else:
            for lo in range(0, len(kept), k):
                slab = stack_batches(kept[lo:lo + k])
                m.train_loop_batch([slab[0]], [slab[1]])
        m.drain_metrics()
        return m

    rec = flight.install_flight_recorder(
        os.path.join(workdir, "guard_flight"))
    out = {}
    try:
        # -- phase A: skip-policy determinism at K ∈ {1, 4}, both
        # fault sites. Poisoned final params hex must equal the clean
        # run over the stream minus the scheduled steps.
        for site in ("data.poison", "grad.nonfinite"):
            for k in (1, 4):
                faults.reset()
                faults.enable(seed=seed)
                faults.inject(site, nth=(4, 11))
                m = run(build(nguard.GuardPolicy(on_nonfinite="skip",
                                                 budget=8)), k)
                assert m._guard.n_skipped == 2, m._guard.status()
                schedule = faults.preview(site, n_batches)
                assert schedule == [4, 11], schedule
                _assert_schedule_matches(faults, (site,))
                poisoned = params_hex(m)
                faults.reset()
                clean = params_hex(run(
                    build(nguard.GuardPolicy(on_nonfinite="skip")),
                    k, skip_idx={c - 1 for c in schedule}))
                assert poisoned == clean, (
                    f"{site} k={k}: skip-policy params {poisoned} != "
                    f"clean-minus params {clean} — skip is not an "
                    f"exact no-op")
                out[f"{site}.k{k}"] = poisoned
        # -- phase B: rollback restores a verified step and completes
        faults.reset()
        faults.enable(seed=seed)
        faults.inject("data.poison", nth=(10,))
        pol = nguard.GuardPolicy(on_nonfinite="rollback",
                                 max_rollbacks=3)
        m = build(pol)
        x = np.concatenate([b[0] for b in batches])
        y = np.concatenate([b[1] for b in batches])
        ck_dir = os.path.join(workdir, "guard_ck")
        m.fit(TensorDataset([x, y]), batch_size=batch, epochs=2,
              shuffle=False, verbose=0, checkpoint_dir=ck_dir,
              checkpoint_freq=3, keep_checkpoints=4)
        assert pol.n_rollbacks >= 1, pol.status()
        mgr = CheckpointManager(ck_dir, async_save=False)
        steps = mgr.verified_steps()
        mgr.close()
        assert steps and steps[-1] == m._step_count, (
            f"rollback run did not finish with a verified final "
            f"checkpoint: {steps} vs step {m._step_count}")
        faults.reset()
        out["rollback"] = {"rollbacks": pol.n_rollbacks,
                           "final_step": int(m._step_count)}
        # -- phase C: guard-off zero overhead — the lowered program
        # has no finite-check ops (the one-flag-check discipline made
        # structural), plus a wall-clock sanity bound vs guard-on
        moff = build(None)
        x0, y0 = batches[0]
        moff.train_batch([x0], [y0])
        lowered = moff._train_step_fn.lower(
            moff._params, moff._frozen, moff._opt_state,
            moff._buffers, moff._step_count, jax.random.key(0),
            (x0,), (y0,)).as_text()
        assert "is_finite" not in lowered, (
            "guard-off train step still contains finite-check ops — "
            "the disabled path is not zero-overhead")
        assert moff._guard is None and not moff._guard_pending
        mon = build(nguard.GuardPolicy(on_nonfinite="skip"))
        mon.train_batch([x0], [y0])

        def med_step(m):
            ts = []
            for _ in range(30):
                t0 = time.perf_counter()
                m.train_batch([x0], [y0])
                ts.append(time.perf_counter() - t0)
            return float(np.median(ts))

        t_off, t_on = med_step(moff), med_step(mon)
        moff.drain_metrics()
        mon.drain_metrics()
        assert t_off <= t_on * 1.5 + 2e-3, (
            f"guard-OFF per-step time {t_off * 1e3:.2f}ms vs guard-on "
            f"{t_on * 1e3:.2f}ms — the disabled path must not cost "
            f"more than one flag check")
        out["bench"] = {"off_ms": round(t_off * 1e3, 3),
                        "on_ms": round(t_on * 1e3, 3)}
    except AssertionError as e:
        path = rec.dump("guard_soak_failure",
                        extra={"what": "guard_soak_assertion",
                               "seed": seed, "error": str(e),
                               "injected": faults.injected_log()})
        print(f"GUARD SOAK FAILED under fault seed {seed}\n"
              f"replay: python tools/chaos_soak.py --train "
              f"--seed {seed}\nflight dump: {path}",
              file=sys.stderr, flush=True)
        raise
    finally:
        faults.reset()
        rec.uninstall()
    return out


def _train_soak_inprocess(seed: int, workdir: str) -> dict:
    """Train-soak invariants that don't need a subprocess."""
    import glob

    from paddle_tpu.io.checkpoint import (CheckpointManager,
                                          latest_manifest_step)
    from paddle_tpu.reliability import faults
    from paddle_tpu.reliability.faults import FaultInjected

    out = {}
    # -- async stall bound: slow the commit path 0.4s; save() must
    # return in snapshot time while the barrier sees the full commit
    d = os.path.join(workdir, "stall_ck")
    mgr = CheckpointManager(d, async_save=True)
    orig_commit = mgr._commit
    mgr._commit = lambda *a, **kw: (time.sleep(0.4),
                                    orig_commit(*a, **kw))[-1]
    t0 = time.perf_counter()
    mgr.save(1, {"w": np.zeros((128, 128), np.float32)},
             state={"step": 1})
    stall = time.perf_counter() - t0
    t0 = time.perf_counter()
    mgr.wait_until_finished()
    commit_wall = time.perf_counter() - t0
    assert stall < 0.2 and commit_wall >= 0.3, (
        f"async save stalled the train loop {stall:.3f}s against a "
        f"{commit_wall:.3f}s commit — the stall must be bounded by "
        f"the device→host snapshot, not the write")
    mgr._commit = orig_commit
    mgr.close()
    out["stall"] = {"save_call_s": round(stall, 4),
                    "commit_s": round(commit_wall, 3)}

    # -- corrupt newest checkpoint: quarantined on restore, falls back
    # to the newest VERIFIED step, never surfaces via latest_step again
    ckdir = os.path.join(workdir, "train_base_k1", "ckpt")
    mgr = CheckpointManager(ckdir, async_save=False)
    newest = mgr.latest_step()
    # flip a byte every 32 across EVERY file of the step: a single
    # mid-file flip can (correctly) be invisible when it lands in
    # ocdbt btree dead space a restore never reads — rot THIS thorough
    # must either corrupt restored values (digest mismatch) or break
    # the read outright (also quarantined)
    corrupted = 0
    for f in glob.glob(os.path.join(ckdir, str(newest), "**"),
                       recursive=True):
        if not os.path.isfile(f):
            continue
        blob = bytearray(open(f, "rb").read())
        for i in range(0, len(blob), 32):
            blob[i] ^= 0xFF
        open(f, "wb").write(bytes(blob))
        corrupted += 1
    assert corrupted, f"no payload files found under step {newest}"
    _tree, state = mgr.restore_with_state()
    fallback = mgr.latest_step()
    assert fallback is not None and fallback < newest, (
        f"corrupt step {newest} still surfaced: latest={fallback}")
    assert int(state["step"]) == fallback, state
    assert latest_manifest_step(ckdir) == fallback, (
        "quarantined step still visible to the elastic launcher")
    mgr.close()
    out["corrupt"] = {"newest": int(newest), "fallback": int(fallback)}

    # -- seeded replay at the new checkpoint fault sites
    faults.reset()
    faults.enable(seed=seed)
    faults.inject("ckpt.snapshot", nth=(2,), times=1)
    faults.inject("ckpt.async_commit", nth=(2,), times=1)
    d2 = os.path.join(workdir, "site_ck")
    m2 = CheckpointManager(d2, async_save=True)
    try:
        m2.save(1, {"w": np.arange(8)})
        m2.wait_until_finished()
        try:
            m2.save(2, {"w": np.arange(8)})
            raised = False
        except FaultInjected:
            raised = True   # snapshot fault hits the CALLER, in-line
        assert raised, "ckpt.snapshot fault did not surface"
        m2.save(3, {"w": np.arange(8)})
        try:
            m2.wait_until_finished()
            raised = False
        except FaultInjected:
            raised = True   # commit fault surfaces at the barrier
        assert raised, "ckpt.async_commit fault did not surface"
        assert m2.latest_step() == 1, (
            f"a faulted commit surfaced: {m2.latest_step()}")
        _assert_schedule_matches(
            faults, ("ckpt.snapshot", "ckpt.async_commit"))
    finally:
        m2.close()
        faults.reset()
    out["fault_sites"] = {"injected": 2}
    return out


def _train_worker(run_dir: str, k: int, freq: int) -> int:
    """Subprocess body for the train soak: fit with async full-state
    checkpointing + resume="auto" + PreemptionGuard, announcing phase
    markers so the parent can land kills inside specific windows.
    Appends one "step loss-hex" line per optimizer step to losses.txt
    (hex floats: the bit-identity assertion needs exact values)."""
    import paddle_tpu as pt
    from paddle_tpu import nn
    from paddle_tpu.distributed import elastic
    from paddle_tpu.io import TensorDataset
    from paddle_tpu.io import checkpoint as ckpt_mod

    # the persistent compile cache (Model.prepare turns it on, at the
    # one path core/compile_cache.py names): relaunches (the whole
    # point of this soak) skip the XLA compile after the first
    # incarnation

    # phase markers for the parent's kill targeting (patch ONCE — the
    # merged-baseline mode calls this body twice in one process)
    Mgr = ckpt_mod.CheckpointManager
    if not getattr(Mgr, "_soak_markers", False):
        orig_save, orig_commit, orig_gc = Mgr.save, Mgr._commit, Mgr._gc

        def save(self, step, tree, force=False, async_=None, state=None):
            print(f"SNAPSHOT {step}", flush=True)
            return orig_save(self, step, tree, force=force,
                             async_=async_, state=state)

        def commit(self, step, tree, force, state):
            print(f"COMMIT {step}", flush=True)
            return orig_commit(self, step, tree, force, state)

        def gc(self):
            print("GC 0", flush=True)
            return orig_gc(self)

        Mgr.save, Mgr._commit, Mgr._gc = save, commit, gc
        Mgr._soak_markers = True

    pt.seed(0)
    net = nn.Sequential(nn.Linear(8, 16), nn.Tanh(), nn.Linear(16, 4))
    model = pt.Model(net)
    model.prepare(
        optimizer=pt.optimizer.AdamW(learning_rate=1e-2, parameters=net),
        loss=nn.CrossEntropyLoss(), metrics=pt.metric.Accuracy())
    rng = np.random.RandomState(3)
    n = TRAIN_EPOCH_STEPS * 4
    x = rng.randn(n, 8).astype(np.float32)
    y = rng.randint(0, 4, (n, 1))
    loss_path = os.path.join(run_dir, "losses.txt")

    class LossWriter(pt.callbacks.Callback):
        """One "global-step loss-hex" line per optimizer step. fit's
        in-epoch ``step`` is resume-aware (a mid-epoch resume starts at
        the restored cursor), so epoch*steps + step IS the global
        step."""

        def on_epoch_begin(self, epoch, logs=None):
            self._epoch = epoch

        def on_train_batch_end(self, step, logs=None):
            g = self._epoch * TRAIN_EPOCH_STEPS + step
            with open(loss_path, "a") as f:
                f.write(f"{g} {float(logs['loss']).hex()}\n")
            print(f"STEP {g}", flush=True)

    guard = elastic.PreemptionGuard()
    model.fit(TensorDataset([x, y]), batch_size=4, epochs=2,
              shuffle=True, verbose=0, steps_per_loop=k,
              callbacks=[LossWriter()],
              checkpoint_dir=os.path.join(run_dir, "ckpt"),
              checkpoint_freq=freq, resume="auto", keep_checkpoints=3,
              preemption_guard=guard, preemption_flush_budget=20.0)
    print("DONE", flush=True)
    return 0


def _ckpt_worker(directory: str, n_steps: int) -> int:
    """Subprocess body for the SIGKILL scenario: announce, then save —
    the parent kills inside an announced window."""
    from paddle_tpu.io.checkpoint import CheckpointManager
    mgr = CheckpointManager(directory, async_save=False, max_to_keep=4)
    for step in range(n_steps):
        print(f"SAVING {step}", flush=True)
        mgr.save(step, {"w": np.arange(2048, dtype=np.int64) + step,
                        "step": np.asarray(step)})
        print(f"SAVED {step}", flush=True)
    mgr.close()
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--ci", action="store_true",
                    help="fixed seeds, one pass per scenario "
                         "(~30s compute budget; ~50s with --fleet)")
    ap.add_argument("--fleet", action="store_true",
                    help="run ONLY the fleet scenario (router + K=3 "
                         "replica subprocesses, SIGKILL mid-decode)")
    ap.add_argument("--train", action="store_true",
                    help="run ONLY the train scenario (kill-anywhere "
                         "fit workers, bit-identical resume)")
    ap.add_argument("--slab", action="store_true",
                    help="run ONLY the fused-decode-slab scenario "
                         "(decode_ticks_per_dispatch=8 under an "
                         "engine.slab kill/cancel/deadline storm)")
    ap.add_argument("--autoscale", action="store_true",
                    help="run ONLY the autoscaler scenario (burn-"
                         "tripped scale-out with a seeded spawn "
                         "fault, SIGKILL → replacement, fault-forced "
                         "straggler drain → token-identical failover)")
    ap.add_argument("--overload", action="store_true",
                    help="run ONLY the brownout scenario (3× burst "
                         "storm, typed resolution, gold zero loss, "
                         "dwell-bounded ladder walk, seeded "
                         "overload.estimate/step faults)")
    ap.add_argument("--seed", type=int, default=1234)
    ap.add_argument("--workdir", default=None)
    ap.add_argument("--ckpt-worker", nargs=2, metavar=("DIR", "STEPS"),
                    help=argparse.SUPPRESS)
    ap.add_argument("--train-worker", nargs=3,
                    metavar=("DIR", "K", "FREQ"),
                    help=argparse.SUPPRESS)
    ap.add_argument("--train-baseline", nargs=3,
                    metavar=("DIR_K1", "DIR_K4", "FREQ"),
                    help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    if args.ckpt_worker:
        return _ckpt_worker(args.ckpt_worker[0],
                            int(args.ckpt_worker[1]))
    if args.train_worker:
        return _train_worker(args.train_worker[0],
                             int(args.train_worker[1]),
                             int(args.train_worker[2]))
    if args.train_baseline:
        # both uninterrupted baselines in one process: pays the jax
        # import once; each _train_worker call re-seeds and rebuilds
        # its model from scratch
        freq = int(args.train_baseline[2])
        _train_worker(args.train_baseline[0], 1, freq)
        return _train_worker(args.train_baseline[1], 4, freq)
    seed = 1234 if args.ci else args.seed
    workdir = args.workdir or os.path.join(
        "/tmp", f"pt_chaos_{os.getpid()}")
    os.makedirs(workdir, exist_ok=True)

    t0 = time.monotonic()
    out = {"seed": seed}
    try:
        if args.fleet:
            out["fleet"] = fleet_soak(seed, workdir)
            # ISSUE 18: the disaggregated prefill/decode fleet under
            # migration chaos (corrupt page in flight, prefill killed
            # mid-pull, seeded router.migrate fault) — every mode
            # falls back to token-identical local recompute
            out["disagg"] = disagg_soak(seed, workdir)
            # ISSUE 19: the stream-integrity auditor under a drift
            # storm — seeded audit.flip caught at the exact divergent
            # position (shadow + device-retry prefix), one-shot
            # flight dump, clean storms record zero divergences
            out["drift"] = drift_soak(seed, workdir)
        elif args.autoscale:
            out["autoscale"] = autoscale_soak(seed, workdir)
        elif args.overload:
            out["overload"] = overload_soak(seed, workdir)
        elif args.train:
            out["train"] = train_soak(seed, workdir)
        elif args.slab:
            out["slab"] = slab_soak(seed)
            # ISSUE 15: the same kill/cancel/deadline storm on an
            # int8-quantized pool — nonce-pinned identity vs an int8
            # reference
            out["slab_int8"] = slab_soak(seed, kv_dtype="int8")
            out["page_pressure"] = page_pressure_soak(seed)
            # ISSUE 15: same storm, same pool HBM, int8 pages —
            # >=1.8x usable pages, scale_table row, headroom re-pin
            out["page_pressure_int8"] = page_pressure_soak(
                seed, kv_dtype="int8")
            # ISSUE 17: the storm again with on-device speculative
            # rounds (int8 draft pool + cache + N=8) —
            # nonce-pinned identity incl. temperature>0 rejection
            # sampling, rejected-draft pages leak-free
            out["slab_spec"] = spec_slab_soak(seed)
        else:
            out["engine"] = engine_soak(seed)
            out["ckpt"] = ckpt_crash(seed, workdir)
            out["flight"] = flight_escalation(seed, workdir)
            out["goodput"] = goodput_soak(seed, workdir)
    except AssertionError:
        # make a red CI run reproducible in one copy-paste: the seed
        # IS the fault schedule (docs/RELIABILITY.md determinism)
        replay = (f"python tools/chaos_soak.py --seed {seed}"
                  + (" --fleet" if args.fleet else "")
                  + (" --autoscale" if args.autoscale else "")
                  + (" --overload" if args.overload else "")
                  + (" --train" if args.train else "")
                  + (" --slab" if args.slab else ""))
        print(f"CHAOS SOAK FAILED under fault seed {seed}\n"
              f"replay: {replay}", file=sys.stderr, flush=True)
        raise
    out["wall_s"] = round(time.monotonic() - t0, 1)
    print("chaos soak OK: " + json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
