#!/usr/bin/env python3
"""One run of one cell of BENCHMARK.json.

    python benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

A new process: loads, warms up, measures for ``--seconds``, checks what the
timed path produced against the plain reference, prints one JSON object as the
last line of its standard output and exits. Everything else it prints stands
on earlier lines, one JSON object each. ``--control 1`` also reads the
reference put in the program's place at the next lower precision (the control
of the check; the benchmark's own runs do not ask for it).

Driven by data: the cell names a configuration (``configs/<name>.json``, which
names its kind of system, ``systems/<kind>.py``) and a traffic mix
(``workloads/<name>.json``, which names its kind of generator,
``traffic/<kind>.py``); each per-layer metric is read by
``layer_metrics/<name>.py``. A name that is not a cell of BENCHMARK.json is
looked up as ``workloads/<name>.json`` with a ``"config"`` key of its own: the
rehearsal cells, which run anywhere and say so.
"""

from __future__ import annotations

import time

_T0 = time.monotonic()

import argparse  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import threading  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def say(obj) -> None:
    print(json.dumps(obj), flush=True)


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def resolve(name: str, bench: dict) -> dict:
    """The cell: its configuration, mix, chips and the metrics it reports."""
    cell = next((w for w in bench["workloads"] if w["name"] == name), None)
    if cell is None:
        path = os.path.join(BENCH_DIR, "workloads", name + ".json")
        if not os.path.exists(path):
            raise SystemExit(f"no cell or rehearsal mix named {name!r}")
        mix = load_json(path)
        if "config" not in mix:
            raise SystemExit(f"{name!r} is a mix, not a cell: name a cell of "
                             f"BENCHMARK.json")
        cell = {"name": name, "config": mix["config"], "traffic": name,
                "chips": int(mix.get("chips", 1)), "like": mix.get("like")}
    entry = next((c for c in bench["configs"] if c["name"] == cell["config"]),
                 None)
    cfg_path = os.path.join(ROOT, entry["file"]) if entry else os.path.join(
        BENCH_DIR, "configs", cell["config"] + ".json")
    config = load_json(cfg_path)
    mix = load_json(BENCH_DIR, "workloads", cell["traffic"] + ".json")
    check = load_json(BENCH_DIR, "checks", cell["name"] + ".json")
    if entry is None and not config.get("rehearsal"):
        raise SystemExit(f"configuration {cell['config']!r} is neither in "
                         f"BENCHMARK.json nor marked as a rehearsal")
    like = cell.get("like") or cell["name"]

    def reports(metric):
        return like in metric.get(
            "workloads", [w["name"] for w in bench["workloads"]])

    return {"cell": cell, "config": config, "mix": mix, "check": check,
            "rehearsal": bool(config.get("rehearsal")),
            "end_to_end": [m for m in bench["end_to_end"] if reports(m)],
            "per_layer": [m for m in bench["per_layer"] if reports(m)]}


def read_layer_metric(name: str, facts: dict, trace):
    path = os.path.join(BENCH_DIR, "layer_metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "layer_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(facts, trace)


class Run:
    """What a system driver is handed, and what it tells back."""

    def __init__(self, args, res):
        self.seed, self.seconds = args.seed, float(args.seconds)
        self.trace, self.control = bool(args.trace), bool(args.control)
        self.config, self.workload = res["config"], res["mix"]
        self.check = res["check"]
        self.chips = res["cell"]["chips"]
        self.bench_dir = BENCH_DIR
        self.marks = {}
        self.t_window = None
        self.memory_peak = None
        self._compiles = 0
        self._last_compile = time.monotonic()
        self._trace_dir = None
        self._tracer = None
        self.say = say

    # set-up bookkeeping
    def mark(self, name: str) -> None:
        self.marks[name] = time.monotonic() - _T0

    def window_opens(self) -> None:
        self.t_window = time.monotonic()

    # compilations, counted from JAX's own events
    def on_event(self, name, *a, **kw) -> None:
        if name == COMPILE_EVENT:
            self._compiles += 1
            self._last_compile = time.monotonic()

    def compile_count(self) -> int:
        return self._compiles

    def compile_quiet_for(self) -> float:
        return time.monotonic() - self._last_compile

    # the profiler
    def start_trace(self) -> None:
        import jax
        self._trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
        kw = {}
        try:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            kw["profiler_options"] = opts
        except AttributeError:
            pass
        jax.profiler.start_trace(self._trace_dir, **kw)

    def stop_trace(self) -> None:
        import jax
        jax.profiler.stop_trace()

    def trace_between(self, t_a: float, t_b: float) -> None:
        def body():
            time.sleep(max(0.0, t_a - time.monotonic()))
            self.start_trace()
            time.sleep(max(0.0, t_b - time.monotonic()))
            self.stop_trace()
        self._tracer = threading.Thread(target=body, daemon=True)
        self._tracer.start()

    def trace_join(self) -> None:
        if self._tracer is not None:
            self._tracer.join()

    def reduced_trace(self):
        if self._trace_dir is None:
            return None
        from benchmark import trace_reduce
        try:
            return trace_reduce.reduce_file(
                trace_reduce.find_xplane(self._trace_dir))
        finally:
            shutil.rmtree(self._trace_dir, ignore_errors=True)

    def read_memory_peak(self) -> None:
        import jax
        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
                 for d in jax.local_devices()]
        peaks = [p for p in peaks if p is not None]
        self.memory_peak = max(peaks) if peaks else None


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    ap.add_argument("--set", action="append", default=[], metavar="KEY=JSON",
                    help="override a key of the traffic mix, for a sweep; "
                         "the result line says so")
    args = ap.parse_args(argv)
    sys.path.insert(0, ROOT)
    bench = load_json(ROOT, "BENCHMARK.json")
    res = resolve(args.workload, bench)
    for item in args.set:
        key, _, value = item.partition("=")
        res["mix"][key] = json.loads(value)

    import jax
    import paddle_tpu  # noqa: F401 — the system under test; absent: fail
    from paddle_tpu.core import compile_cache
    from benchmark import roofline
    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    if res["rehearsal"]:
        say({"rehearsal": True, "note": "a walk through the harness; no "
             "number below is a measurement of the device", **device})
        peaks = None
    else:
        if device["platform"] != "tpu":
            print(f"benchmark: jax found platform {device['platform']!r}, "
                  f"not a TPU: no result", file=sys.stderr)
            return 2
        if device["count"] < res["cell"]["chips"]:
            print(f"benchmark: the cell asks for {res['cell']['chips']} "
                  f"chip(s), jax reports {device['count']}", file=sys.stderr)
            return 2
        peaks = roofline.peaks_for(device["kind"])
    cache_dir, origin = compile_cache.enable()
    run = Run(args, res)
    jax.monitoring.register_event_duration_secs_listener(run.on_event)
    run.mark("import")
    say({"cell": res["cell"]["name"], "config": res["cell"]["config"],
         "traffic": res["cell"]["traffic"], "seed": args.seed,
         "seconds": args.seconds, "trace": args.trace, "device": device,
         "jax": jax.__version__, "compile_cache_dir": cache_dir,
         "compile_cache_dir_from": origin})

    system = importlib.import_module(
        "benchmark.systems." + res["config"]["system"])
    out = system.run(run)
    setup_s = run.t_window - _T0
    marks = run.marks
    parts, prev = {}, 0.0
    for name in ("import", "weights", "engine", "warmup"):
        if name in marks:
            parts[name] = round(marks[name] - prev, 3)
            prev = marks[name]
    say({"setup_s": setup_s, "setup_parts_s": parts})

    device["memory_peak_bytes"] = run.memory_peak
    line = {"correct": bool(out["correct"]), "attempted": out["attempted"],
            "failed": out["failed"], "metrics": {}, "device": device}
    values = dict(out["end_to_end"], setup_s=setup_s)
    if not args.trace:
        for m in res["end_to_end"]:
            if m["name"] in values:
                line["metrics"][m["name"]] = {"value": values[m["name"]],
                                              "unit": m["unit"]}
    else:
        trace = run.reduced_trace()
        facts = dict(out["facts"], end_to_end=values, peaks=peaks,
                     chips=res["cell"]["chips"])
        for m in res["per_layer"]:
            v = read_layer_metric(m["name"], facts, trace)
            if v is not None:
                line["metrics"][m["name"]] = {"value": v, "unit": m["unit"]}
        if trace is not None and trace["devices"]:
            device["busy_s"] = trace["busy_s"]
            device["window_s"] = trace["window_s"]
            line["breakdown"] = {"device_ops": trace["top_ops"],
                                 "idle_gaps": trace["idle_gaps"]}
            say({"trace": {"devices": trace["devices"],
                           "clock_offset_s": trace["clock_offset_s"],
                           "programs": {k: {"executions": len(v),
                                            "seconds": sum(v)}
                                        for k, v in
                                        trace["programs"].items()}}})
    if res["rehearsal"]:
        line["rehearsal"] = True
    if args.set:
        line["overrides"] = args.set
    say(line)
    return 0


if __name__ == "__main__":
    sys.exit(main())
