#!/usr/bin/env python3
"""Device self time per program per ``jax.named_scope`` of a profiler trace.

    python tools/trace_scopes.py <trace dir or .xplane.pb> [--full] [--top N]

The trace is whatever the JAX profiler wrote: ``paddle.profiler.Profiler``'s
``log_dir``, the directory ``POST /profilez`` names, a benchmark's trace.
``--full`` keeps each operation's whole path instead of its scope. Reads the
file with ``paddle_tpu/profiler/scopes.py`` alone (no jax, no chip).
"""
import argparse
import importlib.util
import os
import sys


def _scopes():
    # by file, not through the package: ``import paddle_tpu`` loads jax
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "paddle_tpu", "profiler", "scopes.py")
    spec = importlib.util.spec_from_file_location("_trace_scopes", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("trace")
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--top", type=int, default=12)
    args = ap.parse_args(argv)
    scopes = _scopes()
    table = scopes.by_scope(args.trace,
                            key=scopes.op_path if args.full
                            else scopes.scope_of)
    print(scopes.format_table(table, top=args.top))
    return 0


if __name__ == "__main__":
    sys.exit(main())
