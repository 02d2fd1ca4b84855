#!/usr/bin/env bash
# CI gate (analog of the reference's paddle_build.sh test stages +
# tools/ci_model_benchmark.sh): suite on the virtual 8-device CPU mesh,
# the driver's multichip dry-runs, a CPU bench smoke, and an
# install-from-wheel import check.
set -euo pipefail
cd "$(dirname "$0")/.."

# --smoke: fast tier only — skips @pytest.mark.slow except tests ALSO
# marked @pytest.mark.smoke (representative picks inside all-slow files,
# so pipeline/optest keep smoke coverage); full suite remains the merge gate.
PYTEST_ARGS=()
TIER=""
if [[ "${1:-}" == "--smoke" ]]; then
  PYTEST_ARGS=(-m "not slow or smoke")
  TIER=" [smoke]"
fi

echo "== unit + integration suite (8-device CPU mesh)${TIER}"
python -m pytest tests/ -q -o faulthandler_timeout=300 "${PYTEST_ARGS[@]}"

echo "== multichip dryrun (n=8 and n=4)"
python -c "import jax; jax.config.update('jax_platforms','cpu'); \
jax.config.update('jax_num_cpu_devices', 8); \
import __graft_entry__ as g; g.dryrun_multichip(8)"
python -c "import jax; jax.config.update('jax_platforms','cpu'); \
jax.config.update('jax_num_cpu_devices', 8); \
import __graft_entry__ as g; g.dryrun_multichip(4)"

echo "== observability smoke (train loop -> prometheus + chrome trace"
echo "   + jsonl + debug-server scrape + flight-recorder crash dump)"
python tools/obs_smoke.py "$(mktemp -d)"

echo "== fleet observability smoke (K=2 replicas -> /fleetz federation"
echo "   + one cross-process trace + disabled-tracing flag-check bound)"
# router + 2 spawned replicas: /fleetz aggregates replica-labeled
# series, a request's router.dispatch -> llm.request spans share ONE
# trace_id over real HTTP (fetched back via /tracez?trace_id=),
# trace_merge joins the tables, and disabled tracing still costs one
# flag check (time-bounded). ISSUE-19 rider: the stream auditor arms
# on router traffic — /driftz 404s pre-arm, reports verified chains
# post-traffic, and fleet_drift_* federates with hole-not-zero
# semantics (a never-armed replica is a hole, not a clean zero)
python tools/obs_smoke.py "$(mktemp -d)" --fleet

echo "== llm serving smoke (prefix cache + chunked ragged prefill"
echo "   + decode-ticks sweep)"
# 4 shared-prefix prompts through the engine: asserts nonzero cache
# hits, cache-on == cache-off generations, a clean shutdown, and the
# fused decode-slab sweep
python tools/llm_bench.py --ci

echo "== kv-dtype bench (bf16 vs int8 KV pool at fixed HBM)"
# quantized-tolerance gate: int8 retains >=1.8x bf16's prefix-cache
# pages at the same pool HBM budget, int8 streams are internally
# exact (cache on/off identical — deterministic quantization) and
# agree with the f32 pool within the documented tolerance; ledger
# rows are kv_dtype-keyed so int8/bf16 never gate against each other
python tools/llm_bench.py --ci --kv-dtype

echo "== speculative slab bench (on-device draft-K/verify-1 rounds)"
# the spec slab sweep (K x kv_dtype x prefix cache) must emit greedy
# tokens identical to a target-only engine in every combination;
# per-combination bench_ledger/v1 rows key draft K + cache state into
# the series so K=2 never gates against K=8
python tools/llm_bench.py --ci --spec

echo "== chaos soak (seeded fault injection -> hardened semantics)"
# engine under injected device faults + deadlines/shed/cancel storm,
# SIGKILL mid-checkpoint-save, and an io.worker fault escalating to a
# flight-recorder dump; fails on any hung future, leaked KV page,
# unreplayable fault schedule, or unrestorable checkpoint
python tools/chaos_soak.py --ci

echo "== fused-slab chaos soak (decode_ticks_per_dispatch=8"
echo "   + int8 riders)"
# engine.slab kill storm at the fused slab dispatch + cancel/deadline
# storms landing mid-slab: every future resolves, retried streams are
# token-identical to a fault-free reference engine, zero KV-page
# leaks, fault schedule replays from seed. ISSUE-15 riders: the same
# storm on an int8 pool, and the
# page-pressure storm repeated at fixed HBM with kv_dtype=int8
# (>=1.8x usable pages, 2x slots before slab-shrink engages,
# scale_table ledger row, headroom gauge semantics re-pinned)
python tools/chaos_soak.py --ci --slab

echo "== fleet chaos soak (K=3 replicas, SIGKILL mid-decode -> failover)"
# router + 3 spawned replica subprocesses over TCPStore membership:
# injected faults drain one replica (no new admissions within a poll
# interval; POST /reset_health recovers it), SIGKILL mid-decode loses
# zero requests (token-identical failover), the breaker walks
# open -> half-open -> closed across a respawn; /fleetz aggregates the
# fleet and a deadline-miss storm moves /sloz burn rates + latches the
# breach; failures attach a merged cross-process trace. Then the
# disagg phase: a prefill-pool replica feeds two decode replicas via
# KV-page migration — a SIGKILLed prefill replica and a corrupted
# in-flight page both degrade to local recompute (token-identical,
# zero pages leaked). Then the ISSUE-19 drift storm: a seeded
# audit.flip corrupts one emitted token BEFORE chain extension (the
# corrupted stream is self-consistent, so only chain-vs-chain checks
# catch it) — the shadow re-execution names the exact divergent
# position, fires ONE flight dump carrying both digests + knob
# fingerprints, a mid-decode device retry is verified prefix-intact,
# clean storms report zero divergences, and the fault schedule
# replays from seed
python tools/chaos_soak.py --ci --fleet

echo "== autoscale chaos soak (SLO-driven scale-out/in over a live fleet)"
# the ISSUE-13 gate, half 1: a gold-class deadline-miss storm trips
# both burn windows -> scale-out (first spawn attempt dies on the
# seeded autoscale.spawn fault; the retry absorbs it with no ghost
# capacity); SIGKILL of the autoscaled replica mid-decode loses zero
# requests (nonce-pinned token-identical failover) and respawns as a
# REPLACEMENT, not a scale-out; a seeded autoscale.drain fault expires
# the scale-in drain deadline with stragglers in flight, which
# complete token-identically on a sibling; membership is withdrawn
# immediately; both sites replay from seed. Failures attach the
# merged cross-process trace next to the seed + replay command.
python tools/chaos_soak.py --ci --autoscale

echo "== overload chaos soak (seeded 3x burst storm -> brownout ladder)"
# the ISSUE-20 gate, half 1: a burst storm over a static K=2 fleet
# engages the brownout ladder (level >= 1, one-level moves only),
# bronze is shed TYPED (OverloadShed with retry_after_s) while gold
# loses ZERO requests, a seeded overload.estimate fault turns a
# wildly-wrong prediction into visible shed/miss verdicts (never a
# hang), a seeded overload.step fault forces a spurious transition the
# hysteresis walks back, and the ladder returns to level 0 after the
# storm; both fault sites replay from seed
python tools/chaos_soak.py --ci --overload

echo "== overload bench (3x burst over static K=2: brownout off vs on)"
# the ISSUE-20 gate, half 2: the same un-scalable burst tape with the
# controller off and on — brownout must hold the gold deadline-hit
# ratio at the UN-overloaded baseline (zero gold lost) and STRICTLY
# cut the wasted-work fraction (deadline misses that burned full
# service time, converted into cheap typed sheds); the comparison
# lands in BENCH_LEDGER.jsonl as llm_overload_* rows
python tools/llm_bench.py --ci --overload

echo "== storm bench (diurnal+burst: static K=3 vs autoscaled fleet)"
# the ISSUE-13 gate, half 2: the millions-of-users-shaped storm
# (shared prefixes, mixed tenants/SLO classes) must trigger >=1
# scale-out and >=1 scale-in with zero lost requests, hold the
# gold-class deadline-hit ratio at least as well as static K=3, and
# spend STRICTLY fewer replica-seconds; the comparison lands in
# BENCH_LEDGER.jsonl as one bench_ledger/v1 row
python tools/llm_bench.py --ci --storm

echo "== train chaos soak (kill-anywhere -> bit-identical resume"
echo "   + poisoned-stream numeric-guard gate)"
# Model.fit with async full-state checkpoints + resume="auto":
# seeded SIGKILLs in the STEP/SNAPSHOT/COMMIT/GC windows plus a
# SIGTERM emergency-flush pass, relaunch to completion, combined loss
# stream bit-identical to the uninterrupted baseline at
# steps_per_loop 1 and 4; async-save stall bounded by snapshot time;
# a byte-rotted newest checkpoint quarantines and falls back without
# ever surfacing through latest_step(); ckpt.* fault sites replay
# from seed. Then the poisoned-stream phase: seeded data.poison /
# grad.nonfinite schedules against the on-device NumericGuard —
# skip-policy final params byte-identical to a clean run minus the
# tripped steps at K in {1,4}, rollback restores a verified step and
# completes, guard-off program carries zero guard ops (failures print
# the seed + replay command and attach a flight dump)
python tools/chaos_soak.py --ci --train

echo "== fleet serving bench (prefix-affinity vs round-robin at K=3)"
# asserts aggregate prefix-cache hit rate with affinity routing is
# >= 1.5x round-robin on the shared-prefix workload
python tools/llm_bench.py --ci --fleet

echo "== disaggregated prefill/decode bench (unified K=3 vs 1P/2D)"
# mixed storm on int8 pools: long uncached prompts migrate as
# digest-verified KV-page runs to the decode pool — short-request
# TTFT p99 must improve at equal aggregate slots, a single-replica
# probe's p99 inter-token gap must be strictly lower with imported
# pages than with local prefills, and generations stay
# token-identical across fleets and probe passes
python tools/llm_bench.py --ci --fleet --disagg

echo "== fused train-loop parity smoke (K=1 vs K=4 bit-identical)"
python tools/train_loop_smoke.py

echo "== fused train-loop dispatch sweep (rehearsal: toy sizes, control flow only)"
python bench.py --rehearse --steps-per-loop 1,8

echo "== bench rehearsal (toy sizes; without --rehearse bench.py needs the chip)"
python bench.py --rehearse

echo "== perf ledger regression gate (BENCH_LEDGER.jsonl trajectory)"
# the llm_bench steps above appended this run's canonical rows (the
# bench.py rehearsals append nothing: they are not measurements); the gate
# fails LOUDLY if the trajectory is empty/unreadable or any series
# regressed past tolerance (wide on CPU, tight on real chips). Rows
# carry the optional drift_divergences field when the stream auditor
# armed during a bench (absent = nobody checked, 0 = checked clean)
python tools/bench_ledger.py --ci

echo "== wheel build + import smoke"
tmp=$(mktemp -d)
pip wheel . --no-deps --no-build-isolation -w "$tmp" -q
ls "$tmp"/*.whl
echo "CI OK"
