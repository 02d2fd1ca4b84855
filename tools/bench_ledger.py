"""The perf ledger: ONE canonical bench-row schema + a regression gate.

Before this tool the perf trajectory was unreadable: bench.py printed
one row shape, llm_bench.py another — no shared keys, no git
anchoring, nothing a gate could diff. This module defines the one row
every bench tool now appends to ``BENCH_LEDGER.jsonl``:

    {"schema": "bench_ledger/v1", "run_id": ..., "ts": ...,
     "git_rev": ..., "backend": ..., "tool": ..., "workload": ...,
     "value": ..., "unit": ..., "tokens_per_sec": ..., "mfu": ...,
     "dispatches": ..., "metrics": {...}, "extra": {...}}

``workload`` + ``backend`` identify a comparable series; ``value`` is
the headline number in ``unit`` (direction: higher is better unless
the row says ``"direction": "lower"``). ``metrics`` carries a bounded
snapshot of the live registry (counters/gauges under the serving and
perf prefixes) so a dead round is visible IN the row.

CLI:
  python tools/bench_ledger.py --compare   # newest row vs trajectory
  python tools/bench_ledger.py --ci        # regression gate (ci.sh)
  python tools/bench_ledger.py --show      # dump the grouped ledger

The ``--ci`` gate fails LOUDLY on an empty/unreadable ledger and on
any series whose newest row regresses below ``(1 - tolerance) x
baseline`` (baseline = median of the prior rows in the series, up to
``--baseline-window``). The default tolerance is deliberately wide on
CPU backends (CI wall clocks are noisy neighbors) and tight on real
chips. The mapping from the legacy row shapes is documented in
PERF.md ("The perf ledger").

Emitters: ``tools/llm_bench.py`` (serving benches), ``bench.py``
(train headline). Path override: ``PT_BENCH_LEDGER`` env (tests point it at a
tmp file; ``PT_BENCH_LEDGER=0`` disables appends entirely).
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import subprocess
import sys
import time
import uuid
from typing import Dict, List, Optional

SCHEMA = "bench_ledger/v1"
REQUIRED = ("schema", "run_id", "ts", "git_rev", "backend", "tool",
            "workload", "value", "unit")

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DEFAULT_PATH = os.path.join(_REPO_ROOT, "BENCH_LEDGER.jsonl")

# default tolerances for the --ci gate: fractional regression allowed
# before the gate fails. CPU CI boxes share cores with neighbors, so
# the CPU bound is wide by design — it catches "fell off a cliff"
# (an accidental host sync, a lost fusion), not 5% noise.
CPU_TOLERANCE = 0.45
HW_TOLERANCE = 0.10
BASELINE_WINDOW = 8

# registry snapshot prefixes a ledger row carries (counters/gauges
# only — histogram percentiles would bloat every row)
METRIC_PREFIXES = ("llm_", "perf_", "mem_", "host_rss_bytes",
                   "train_compile_count", "train_step_count", "fleet_",
                   "goodput_", "badput_", "drift_")


def ledger_path(path: Optional[str] = None) -> Optional[str]:
    """Resolve the ledger path: explicit arg > PT_BENCH_LEDGER env >
    repo-root default. Returns None when appends are disabled
    (``PT_BENCH_LEDGER=0``)."""
    if path:
        return path
    env = os.environ.get("PT_BENCH_LEDGER")
    if env == "0":
        return None
    return env or DEFAULT_PATH


def git_rev() -> str:
    try:
        out = subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"], cwd=_REPO_ROOT,
            capture_output=True, text=True, timeout=10)
        rev = (out.stdout or "").strip()
        return rev if out.returncode == 0 and rev else "unknown"
    except Exception:  # noqa: BLE001 — a revless row beats no row
        return "unknown"


def host_fingerprint() -> str:
    """A machine-class token keying CPU series: wall-clock throughput
    varies 2-5x across hosts, so the regression gate only compares a
    row against prior rows from the SAME class — a slower contributor
    laptop starts its own trajectory instead of failing CI against
    the committed machine's numbers. ``PT_BENCH_HOST`` pins an
    explicit stable name (recommended for long-lived CI fleets whose
    container hostnames are ephemeral)."""
    env = os.environ.get("PT_BENCH_HOST")
    if env:
        return env
    import platform
    return f"{platform.machine()}-{os.cpu_count()}c"


def current_backend() -> str:
    try:
        import jax
        return getattr(jax.devices()[0], "device_kind", "") or \
            jax.default_backend()
    except Exception:  # noqa: BLE001
        return "unknown"


def metrics_snapshot(prefixes=METRIC_PREFIXES) -> Dict[str, float]:
    """Bounded counters/gauges snapshot from the live registry (the
    dead-round witness each row carries). Refreshes the perf_* roofline
    gauges first — they update at read boundaries, and a ledger row IS
    a read boundary."""
    try:
        from paddle_tpu.observability import (default_registry, goodput,
                                              memory, perf)
        if perf.enabled():
            perf.instance().update_gauges()
        if memory.enabled():
            memory.instance().update_gauges()
        if goodput.enabled():
            goodput.instance().update_gauges()
    except Exception:  # noqa: BLE001 — emitters must not need jax up
        return {}
    out: Dict[str, float] = {}
    for fam in default_registry().families():
        if not fam.name.startswith(tuple(prefixes)):
            continue
        if fam.kind == "histogram":
            continue
        for child in fam.children():
            key = fam.name
            if fam.label_names:
                inner = ",".join(
                    f'{n}="{v}"' for n, v in zip(fam.label_names,
                                                 child.label_values))
                key += "{" + inner + "}"
            out[key] = round(float(child.value), 6)
    return out


def goodput_row_fields() -> Dict[str, object]:
    """The time ledger's verdict on the current process — the optional
    ``goodput_fraction`` + ``badput_top`` kwargs a bench row carries
    ({} when the ledger is disabled or never armed, so old-schema rows
    simply lack the keys). All three emitters splat this into
    :func:`append` (the ``peak_mem_bytes`` discipline)."""
    try:
        from paddle_tpu.observability import goodput
        if not goodput.enabled():
            return {}
        led = goodput.instance()
        if not led.armed:
            return {}
        totals = led.totals()
        frac = led.goodput_fraction()
        top = led.top_badput(totals)
        return {
            "goodput_fraction": (round(frac, 4)
                                 if frac is not None else None),
            "badput_top": top["cause"] if top else None,
        }
    except Exception:  # noqa: BLE001 — a row beats no row
        return {}


def drift_row_fields() -> Dict[str, object]:
    """The stream auditor's verdict on the current process — the
    optional ``drift_divergences`` kwarg a bench row carries ({} when
    the auditor is disabled or never armed, so rows keep the
    hole-not-zero semantics: absent means "nobody was checking", 0
    means "checked and clean"). Emitters splat this into
    :func:`append` like :func:`goodput_row_fields`."""
    try:
        from paddle_tpu.observability import audit
        if not audit.enabled():
            return {}
        counts = audit.instance().counts()
        if not counts.get("verified") and not counts.get("diverged"):
            return {}
        return {"drift_divergences": int(counts.get("diverged", 0))}
    except Exception:  # noqa: BLE001 — a row beats no row
        return {}


def make_row(tool: str, workload: str, value: float, unit: str,
             tokens_per_sec: Optional[float] = None,
             mfu: Optional[float] = None,
             dispatches: Optional[float] = None,
             peak_mem_bytes: Optional[float] = None,
             goodput_fraction: Optional[float] = None,
             badput_top: Optional[str] = None,
             drift_divergences: Optional[int] = None,
             backend: Optional[str] = None,
             direction: str = "higher",
             kv_dtype: Optional[str] = None,
             extra: Optional[dict] = None,
             metrics: Optional[dict] = None) -> dict:
    """Build one canonical ledger row (see module docstring).
    ``peak_mem_bytes`` (optional, schema-tolerated when absent — old
    rows predate it) carries the memory ledger's attributed
    high-watermark so capacity changes (int8 KV pages halving pool
    bytes) are visible IN the perf trajectory, next to the
    throughput they bought. ``kv_dtype`` (optional, same absent-field
    tolerance) records the engine KV-pool dtype a serving bench ran
    at AND joins the series key, so an int8 run never regression-
    gates against a bf16 baseline (different storage = different
    trajectory). ``goodput_fraction`` / ``badput_top`` (optional, same
    absent-field tolerance) carry the time ledger's verdict on the
    run — the fraction of bench wall clock the device actually
    computed, and the dominant badput cause — so a throughput number
    bought by hiding stalls outside the timed region is visible IN
    the trajectory row. ``drift_divergences`` (optional, same
    absent-field tolerance) carries the stream auditor's verdict —
    how many audited streams diverged during the run — with hole
    semantics: absent means the auditor never armed, 0 means it
    checked the run and found it clean."""
    return {
        "schema": SCHEMA,
        "run_id": uuid.uuid4().hex[:12],
        "ts": round(time.time(), 3),
        "git_rev": git_rev(),
        "backend": backend if backend is not None else current_backend(),
        "host": host_fingerprint(),
        "tool": str(tool),
        "workload": str(workload),
        "value": float(value),
        "unit": str(unit),
        "tokens_per_sec": (float(tokens_per_sec)
                           if tokens_per_sec is not None else None),
        "mfu": float(mfu) if mfu is not None else None,
        "dispatches": (float(dispatches)
                       if dispatches is not None else None),
        "peak_mem_bytes": (float(peak_mem_bytes)
                          if peak_mem_bytes is not None else None),
        "goodput_fraction": (float(goodput_fraction)
                             if goodput_fraction is not None else None),
        "badput_top": str(badput_top) if badput_top is not None else None,
        "drift_divergences": (int(drift_divergences)
                              if drift_divergences is not None else None),
        "kv_dtype": str(kv_dtype) if kv_dtype is not None else None,
        "direction": direction,
        "metrics": metrics if metrics is not None else metrics_snapshot(),
        "extra": extra or {},
    }


def append_row(row: dict, path: Optional[str] = None) -> Optional[str]:
    """Validate + append one row. Returns the path written (None when
    appends are disabled). Raises ValueError on a malformed row —
    emitting a row the gate can't read is the bug this schema
    exists to kill."""
    missing = [k for k in REQUIRED if row.get(k) is None]
    if missing:
        raise ValueError(f"ledger row missing required fields "
                         f"{missing}: {row}")
    if row["schema"] != SCHEMA:
        raise ValueError(f"unknown ledger schema {row['schema']!r}")
    p = ledger_path(path)
    if p is None:
        return None
    with open(p, "a") as f:
        f.write(json.dumps(row, sort_keys=True) + "\n")
    return p


def append(tool: str, workload: str, value: float, unit: str,
           path: Optional[str] = None, **kw) -> Optional[str]:
    """One-call emitter the bench tools use. Never raises on I/O —
    a failed append must not fail the measurement (schema errors
    still do: those are bugs)."""
    row = make_row(tool, workload, value, unit, **kw)
    try:
        return append_row(row, path=path)
    except OSError as e:
        print(f"bench_ledger: append failed: {e}", file=sys.stderr)
        return None


def read_ledger(path: Optional[str] = None) -> List[dict]:
    """Parse the ledger, skipping malformed lines (reported to
    stderr — a half-written row degrades, never crashes a reader)."""
    p = ledger_path(path)
    if p is None or not os.path.exists(p):
        return []
    rows = []
    with open(p) as f:
        for i, line in enumerate(f):
            line = line.strip()
            if not line:
                continue
            try:
                d = json.loads(line)
            except ValueError:
                print(f"bench_ledger: line {i + 1} unparseable, "
                      f"skipped", file=sys.stderr)
                continue
            if d.get("schema") == SCHEMA and \
                    all(d.get(k) is not None for k in REQUIRED):
                rows.append(d)
            else:
                print(f"bench_ledger: line {i + 1} not a v1 row, "
                      f"skipped", file=sys.stderr)
    return rows


def _series(rows: List[dict]) -> Dict[tuple, List[dict]]:
    """Group by (workload, backend, host, kv_dtype) in file (= time)
    order — host-keying keeps a slower machine's rows from reading as
    a regression of a faster machine's baseline (rows predating the
    host field group under "legacy"), and kv_dtype-keying keeps int8
    and bf16 serving runs in SEPARATE trajectories (rows predating
    the field, or train rows, carry None and group together as
    before)."""
    out: Dict[tuple, List[dict]] = {}
    for r in rows:
        out.setdefault((r["workload"], r["backend"],
                        r.get("host", "legacy"),
                        r.get("kv_dtype")), []).append(r)
    return out


def _tolerance_for(backend: str, override: Optional[float]) -> float:
    if override is not None:
        return override
    b = (backend or "").lower()
    return HW_TOLERANCE if "tpu" in b or "gpu" in b else CPU_TOLERANCE


def compare(rows: List[dict],
            tolerance: Optional[float] = None) -> List[dict]:
    """Per-series verdicts: newest row vs the median of its prior
    rows (up to BASELINE_WINDOW). Single-row series report "new"."""
    verdicts = []
    for (workload, backend, host, kv_dtype), series in sorted(
            _series(rows).items(),
            key=lambda kv: tuple(str(x) for x in kv[0])):
        newest = series[-1]
        prior = series[:-1][-BASELINE_WINDOW:]
        v = {
            "workload": workload,
            "backend": backend,
            "host": host,
            "kv_dtype": kv_dtype,
            "unit": newest["unit"],
            "rows": len(series),
            "newest": newest["value"],
            "newest_rev": newest["git_rev"],
            "newest_mfu": newest.get("mfu"),
            # optional fields (rows predating them have no key at all —
            # .get keeps --compare/--ci tolerant of the old schema)
            "newest_peak_mem_bytes": newest.get("peak_mem_bytes"),
            "newest_goodput_fraction": newest.get("goodput_fraction"),
            "newest_badput_top": newest.get("badput_top"),
            "newest_drift_divergences": newest.get("drift_divergences"),
        }
        if not prior:
            v.update(status="new", baseline=None, ratio=None)
        else:
            baseline = statistics.median(r["value"] for r in prior)
            ratio = newest["value"] / baseline if baseline else None
            tol = _tolerance_for(backend, tolerance)
            lower_better = newest.get("direction") == "lower"
            if ratio is None:
                status = "ok"
            elif lower_better:
                status = "regressed" if ratio > 1.0 + tol else "ok"
            else:
                status = "regressed" if ratio < 1.0 - tol else "ok"
            v.update(status=status, baseline=round(baseline, 4),
                     ratio=round(ratio, 4) if ratio is not None
                     else None, tolerance=tol)
        verdicts.append(v)
    return verdicts


def ci_gate(path: Optional[str] = None,
            tolerance: Optional[float] = None) -> int:
    """The ci.sh regression gate. Exit codes: 0 ok, 2 empty/unreadable
    trajectory (fails LOUDLY — a perf story that reads as [] is itself
    the regression), 3 a series regressed past tolerance."""
    p = ledger_path(path)
    rows = read_ledger(path)
    if not rows:
        print(f"bench_ledger --ci FAIL: no readable rows in "
              f"{p or '(appends disabled)'} — the perf trajectory is "
              f"empty. Run the bench tools (llm_bench.py / bench.py) so "
              f"the ledger has a baseline.",
              file=sys.stderr)
        return 2
    verdicts = compare(rows, tolerance=tolerance)
    bad = [v for v in verdicts if v["status"] == "regressed"]
    for v in verdicts:
        mark = {"ok": "OK ", "new": "NEW", "regressed": "REG"}[
            v["status"]]
        base = (f" baseline {v['baseline']} ratio {v['ratio']}"
                if v.get("baseline") is not None else "")
        kvd = f" kv={v['kv_dtype']}" if v.get("kv_dtype") else ""
        print(f"[{mark}] {v['workload']} @ {v['backend']} "
              f"[{v['host']}]{kvd}: {v['newest']} {v['unit']}{base} "
              f"({v['rows']} rows)")
    if bad:
        print(f"bench_ledger --ci FAIL: {len(bad)} series regressed "
              f"past tolerance:", file=sys.stderr)
        for v in bad:
            print(f"  {v['workload']} @ {v['backend']}: "
                  f"{v['newest']} vs baseline {v['baseline']} "
                  f"(ratio {v['ratio']}, tolerance "
                  f"{v['tolerance']})", file=sys.stderr)
        return 3
    print(f"bench_ledger --ci OK: {len(verdicts)} series, "
          f"{len(rows)} rows, newest rev "
          f"{rows[-1]['git_rev']}")
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--path", default=None,
                    help="ledger file (default: repo BENCH_LEDGER.jsonl "
                         "or $PT_BENCH_LEDGER)")
    ap.add_argument("--compare", action="store_true",
                    help="diff the newest row of each series against "
                         "its trajectory baseline (JSON verdicts)")
    ap.add_argument("--ci", action="store_true",
                    help="regression gate: nonzero exit on an empty "
                         "trajectory or a regressed series")
    ap.add_argument("--show", action="store_true",
                    help="dump the parsed ledger grouped by series")
    ap.add_argument("--tolerance", type=float, default=None,
                    help="override the fractional regression tolerance "
                         "(default: 0.45 CPU, 0.10 TPU/GPU)")
    args = ap.parse_args(argv)

    if args.ci:
        return ci_gate(path=args.path, tolerance=args.tolerance)
    rows = read_ledger(args.path)
    if args.show:
        for key, series in sorted(
                _series(rows).items(),
                key=lambda kv: tuple(str(x) for x in kv[0])):
            kvd = f" kv={key[3]}" if key[3] else ""
            print(f"== {key[0]} @ {key[1]} [{key[2]}]{kvd} "
                  f"({len(series)} rows)")
            for r in series:
                print(f"  {r['git_rev']} {r['value']} {r['unit']} "
                      f"mfu={r.get('mfu')} ts={r['ts']}")
        return 0
    # default + --compare: verdict dump
    print(json.dumps(compare(rows, tolerance=args.tolerance), indent=2))
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except BrokenPipeError:     # `--show | head` is a fine way to read
        sys.exit(0)
