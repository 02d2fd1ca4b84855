"""GPT-2-XL (1.56B) SINGLE-CHIP feasibility, compile-only (VERDICT r4
item 3's chip-independent half: does the 1.5B configuration — Adafactor
factored state + scan/remat + fused vocab loss — fit a 16 GiB v5e?).

Methodology identical to tools/feasibility_1p3b.py: AOT-compile the
REAL train step on one virtual CPU device with abstract
(ShapeDtypeStruct) state and read XLA's compiled memory analysis.
The contrast rows show WHY Adafactor is the lever: AdamW's m+v are
12.5 GiB of fp32 state on top of 6.2 GiB params — no batch fits;
Adafactor's factored second moments are ~MBs.

INTERPRETATION CAVEAT (r5, single-device rows only): the CPU
backend's temp accounting is an UPPER BOUND on the TPU footprint —
it ignores buffer donation entirely (params cannot alias their
updates) and its scheduler optimizes thread parallelism, not peak
memory. Calibration: a gpt2-small forward whose true activation peak
is ~0.6 GiB reads 1.31 GiB here (~2.2x). The bf16+Adafactor rows
reading ~19-20 GiB therefore predict a REAL footprint around
9-12 GiB once donation (-3.1 GiB params alias) and memory-aware
scheduling apply — a chip run is the decider (not measured on
chip). The fp32/AdamW rows are
conclusive the other way: their ARGUMENT bytes alone (state that
must exist, no scheduling involved) exceed the budget.

Run: python tools/feasibility_xl.py [--out FEASIBILITY_XL.json]
     python tools/feasibility_xl.py --child '{"batch":4,...}'
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

_GiB = float(1 << 30)
V5E_BUDGET = 16 * _GiB * 0.85

RUNS = [
    {"batch": 4, "optimizer": "adafactor"},
    {"batch": 8, "optimizer": "adafactor"},
    {"batch": 4, "optimizer": "adamw"},   # the contrast: must NOT fit
    # the fitting configuration: bf16 parameter storage (pure-bf16 +
    # Adafactor, the T5-lineage single-chip recipe; factored state
    # needs no fp32 master copies to stay sublinear)
    {"batch": 4, "optimizer": "adafactor", "param_dtype": "bfloat16"},
    {"batch": 8, "optimizer": "adafactor", "param_dtype": "bfloat16"},
    {"batch": 16, "optimizer": "adafactor", "param_dtype": "bfloat16"},
]


def run_child(spec: dict) -> dict:
    import jax
    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices", 1)

    import numpy as np

    import paddle_tpu as pt
    from paddle_tpu import parallel
    from paddle_tpu.core import rng as rng_mod
    from paddle_tpu.models.gpt import (GPTForCausalLM,
                                       GPTFusedPretrainingCriterion,
                                       gpt_config)
    from paddle_tpu.parallel.planner import abstract_model
    from feasibility_1p3b import _abstract_state

    b = int(spec["batch"])
    seq = int(spec.get("seq", 1024))
    pdt = spec.get("param_dtype")
    cfg = gpt_config("gpt2-xl", hidden_dropout=0.0,
                     attention_dropout=0.0, use_flash=False,
                     remat=True, fused_loss=True, scan_layers=True,
                     max_position_embeddings=seq)
    mesh = parallel.init_mesh(dp=1)
    try:
        pt.seed(0)
        if pdt:
            # bf16 parameter STORAGE from construction (abstract-safe,
            # unlike amp.decorate which casts concrete params); grads
            # and boundary activations inherit the dtype
            from paddle_tpu.core import dtype as dtype_mod
            dtype_mod.set_default_dtype(pdt)
        t0 = time.time()
        net = abstract_model(lambda: GPTForCausalLM(cfg))
        model = pt.Model(net)
        if spec["optimizer"] == "adafactor":
            # factored state is sublinear only without fp32 master
            # copies; Adafactor's own update runs f32 per-tensor
            opt = pt.optimizer.Adafactor(learning_rate=1e-4,
                                         parameters=net,
                                         multi_precision=False)
        else:
            opt = pt.optimizer.AdamW(learning_rate=1e-4,
                                     parameters=net, weight_decay=0.01)
        model.prepare(optimizer=opt,
                      loss=GPTFusedPretrainingCriterion(),
                      amp_configs="O1")
        parallel.distributed_model(model, mesh=mesh)
        state = _abstract_state(model, net, mesh)
        build_s = time.time() - t0

        model._train_step_fn = model._build_train_step()
        ids = np.zeros((b, seq), np.int32)
        inputs = model._shard_batch((ids,))
        labels = model._shard_batch((ids,))
        key = rng_mod.split_for_step(0)
        t0 = time.time()
        lowered = model._train_step_fn.lower(
            *state, 0, key, inputs, labels)
        mem = lowered.compile().memory_analysis()
        compile_s = time.time() - t0
        total = float(mem.temp_size_in_bytes +
                      mem.argument_size_in_bytes)
        opt_bytes = sum(
            int(np.prod(l.shape)) * l.dtype.itemsize
            for l in jax.tree_util.tree_leaves(state[2]))
        return {
            "model": "gpt2-xl", "params": 1557611200,
            "batch": b, "seq": seq,
            "optimizer": spec["optimizer"],
            "opt_state_bytes": float(opt_bytes),
            "argument_bytes": float(mem.argument_size_in_bytes),
            "temp_bytes": float(mem.temp_size_in_bytes),
            "total_bytes": total, "total_gib": total / _GiB,
            "fits_v5e": total <= V5E_BUDGET,
            "build_s": round(build_s, 1),
            "compile_s": round(compile_s, 1),
        }
    finally:
        parallel.set_mesh(None)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="FEASIBILITY_XL.json")
    ap.add_argument("--child", default=None)
    args = ap.parse_args()
    if args.child:
        print(json.dumps(run_child(json.loads(args.child))))
        return
    rows = []
    for spec in RUNS:
        p = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--child",
             json.dumps(spec)],
            capture_output=True, text=True, timeout=3600)
        line = [l for l in p.stdout.splitlines()
                if l.startswith("{")]
        if p.returncode == 0 and line:
            rows.append(json.loads(line[-1]))
        else:
            rows.append({"spec": spec,
                         "error": (p.stderr or "")[-400:]})
        print(json.dumps(rows[-1]), file=sys.stderr)
    with open(args.out, "w") as f:
        json.dump({"budget_gib": V5E_BUDGET / _GiB, "rows": rows}, f,
                  indent=1)


if __name__ == "__main__":
    main()
