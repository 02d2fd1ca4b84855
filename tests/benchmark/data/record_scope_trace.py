#!/usr/bin/env python3
"""How ``probe_scopes.xplane.pb`` beside this file was recorded (on one v5e
chip), for ``paddle_tpu/profiler/scopes.py``:

    python tests/benchmark/data/record_scope_trace.py chiprun_out/trace_scopes

One jitted program, ``probe_scoped``: a ``lax.scan`` of 4 ticks whose body
gathers rows under ``jax.named_scope("probe_gather")`` and multiplies under
``jax.named_scope("probe_scores")``, then a pallas kernel given
``name="probe_kernel"`` under ``jax.named_scope("probe_tail")``. Five rounds
with a 5 ms host sleep between them. It prints each device operation's
``tf_op`` as the decoder reads it, which is what the scope reduction was
written against.
"""
import glob
import os
import shutil
import sys
import time


def build():
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    def double(x_ref, o_ref):
        o_ref[...] = x_ref[...] * 2

    @jax.jit
    def probe_scoped(x, idx):
        def tick(c, _):
            with jax.named_scope("probe_gather"):
                rows = jnp.take(c, idx, axis=0)
            with jax.named_scope("probe_scores"):
                c = jnp.tanh(rows @ c)
            return c, None
        y = jax.lax.scan(tick, x, None, length=4)[0]
        with jax.named_scope("probe_tail"):
            spec = pl.BlockSpec((128, 1024), lambda i: (i, 0))
            return pl.pallas_call(
                double, grid=(8,), in_specs=[spec], out_specs=spec,
                out_shape=jax.ShapeDtypeStruct(y.shape, y.dtype),
                name="probe_kernel")(y)

    return probe_scoped


def main(out: str) -> int:
    import jax
    import jax.numpy as jnp
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"platform {dev.platform!r}: the probe records a TPU trace",
              file=sys.stderr)
        return 2
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))))
    from paddle_tpu.profiler import scopes

    probe_scoped = build()
    x = jnp.full((1024, 1024), 0.001, jnp.bfloat16)
    idx = (jnp.arange(1024, dtype=jnp.int32) * 7) % 1024
    probe_scoped(x, idx).block_until_ready()
    os.makedirs(out, exist_ok=True)
    tdir = os.path.join(out, "trace")
    jax.profiler.start_trace(tdir)
    for _ in range(5):
        with jax.profiler.TraceAnnotation("bench.scoped"):
            probe_scoped(x, idx).block_until_ready()
        with jax.profiler.TraceAnnotation("bench.sleep"):
            time.sleep(0.005)
    jax.profiler.stop_trace()
    path = sorted(glob.glob(os.path.join(tdir, "**", "*.xplane.pb"),
                            recursive=True))[-1]
    shutil.copy(path, os.path.join(out, "probe_scopes.xplane.pb"))
    print("bytes", os.path.getsize(path))
    for plane in scopes.read_planes(path):
        if not plane.name.startswith("/device:"):
            continue
        seen = set()
        for _, _, mid in plane.lines.get("XLA Ops", []):
            if mid not in seen:
                seen.add(mid)
                st = plane.event_stats.get(mid, {})
                print("OP", plane.event_names.get(mid, "?")[:60], "|",
                      st.get("hlo_category"), "|", st.get("tf_op"))
    print(scopes.format_table(scopes.by_scope(path)))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1] if len(sys.argv) > 1
                  else "chiprun_out/trace_scopes"))
