#!/usr/bin/env python3
"""How ``probe.xplane.pb`` beside this file was recorded (on one v5e chip).

    python tests/benchmark/data/record_trace.py chiprun_out/trace_probe

Two named jitted programs (``probe_matmul``, and ``probe_scan`` that holds 4
ticks), five rounds of each with a 20 ms host sleep between them, every call
under a ``TraceAnnotation`` of the kind ``benchmark/run.py`` puts around its
calls into the program. It also prints the trace's planes, lines and first
events, which is what ``benchmark/trace_reduce.py`` was written against.
"""
import glob
import json
import os
import shutil
import sys
import time


def main(out: str) -> int:
    import jax
    import jax.numpy as jnp
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"platform {dev.platform!r}: the probe records a TPU trace",
              file=sys.stderr)
        return 2

    @jax.jit
    def probe_matmul(x):
        return jnp.tanh(x @ x)

    @jax.jit
    def probe_scan(x):
        def tick(c, _):
            return jnp.tanh(c @ c), None
        return jax.lax.scan(tick, x, None, length=4)[0]

    x = jnp.full((1024, 1024), 0.001, jnp.bfloat16)
    probe_matmul(x).block_until_ready()
    probe_scan(x).block_until_ready()
    os.makedirs(out, exist_ok=True)
    tdir = os.path.join(out, "trace")
    jax.profiler.start_trace(tdir)
    t0 = time.perf_counter()
    for i in range(5):
        with jax.profiler.TraceAnnotation("bench.matmul"):
            probe_matmul(x).block_until_ready()
        with jax.profiler.TraceAnnotation("bench.sleep"):
            time.sleep(0.02)
        with jax.profiler.TraceAnnotation("bench.scan"):
            probe_scan(x).block_until_ready()
    window = time.perf_counter() - t0
    jax.profiler.stop_trace()
    path = sorted(glob.glob(os.path.join(tdir, "**", "*.xplane.pb"),
                            recursive=True))[-1]
    shutil.copy(path, os.path.join(out, "probe.xplane.pb"))
    pd = jax.profiler.ProfileData.from_file(path)
    print(json.dumps({"window_s": window, "bytes": os.path.getsize(path)}))
    for plane in pd.planes:
        lines = list(plane.lines)
        print("PLANE", repr(plane.name), len(lines))
        for line in lines:
            events = list(line.events)
            print("  LINE", repr(line.name), len(events))
            for ev in events[:6]:
                stats = {k: (v if isinstance(v, (int, float)) else str(v)[:60])
                         for k, v in list(ev.stats)[:8]}
                print("    EV", repr(ev.name)[:90], ev.start_ns,
                      ev.duration_ns, stats)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1] if len(sys.argv) > 1
                  else "chiprun_out/trace_probe"))
