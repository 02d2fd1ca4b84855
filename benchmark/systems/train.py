"""The training system under test: ``paddle.Model(...).prepare(...).fit(...)``
on one chip or on a ``parallel.init_mesh`` mesh."""

from __future__ import annotations

import gc
import time

from .. import stats, weights
from .serve import build_net


def _fit(model, loader, callback):
    model.fit(loader, epochs=1, verbose=0, shuffle=False,
              callbacks=[callback])


def run(ctx) -> dict:
    import jax
    import jax.numpy as jnp
    import paddle_tpu as pt
    from paddle_tpu import parallel
    from paddle_tpu.hapi.callbacks import Callback
    from paddle_tpu.io import DataLoader, TensorDataset
    from paddle_tpu.models.gpt import GPTFusedPretrainingCriterion
    from ..traffic import train_steps

    cfg, mix = ctx.config, ctx.workload
    model_cfg, tr = cfg, cfg["trainer"]
    d = weights.dims_of(model_cfg)
    hp = tr["optimizer"]
    n_first = int(ctx.check["steps"])

    class Batches(DataLoader):
        """The program's loader type over an in-memory array of batches:
        yields ``(ids, ids)`` until the array or the deadline ends."""

        def __init__(self, data, deadline=None):
            super().__init__(TensorDataset([data[0], data[0]]),
                             batch_size=data.shape[1])
            self.data, self.deadline = data, deadline

        def __len__(self):
            return len(self.data)

        def __iter__(self):
            for ids in self.data:
                if self.deadline is not None \
                        and time.monotonic() >= self.deadline:
                    return
                with jax.profiler.TraceAnnotation("bench.next_batch"):
                    x = jnp.asarray(ids)
                yield x, x

    class Steps(Callback):
        """Stamps each step's end once its loss is on the host."""

        def __init__(self):
            super().__init__()
            self.losses, self.ends = [], []

        def on_train_batch_end(self, step, logs=None):
            with jax.profiler.TraceAnnotation("bench.fetch_loss"):
                self.losses.append(float(logs["loss"]))
            self.ends.append(time.monotonic())

    params = weights.make(d, ctx.seed, jnp.float32)
    jax.block_until_ready(params)
    mesh = None
    if tr.get("mesh"):
        mesh = parallel.init_mesh(**tr["mesh"])
    try:
        net = build_net(model_cfg, params, use_flash=tr["use_flash"],
                        fused_loss=tr["fused_loss"])
        del params
        model = pt.Model(net)
        model.prepare(
            optimizer=pt.optimizer.AdamW(
                learning_rate=hp["lr"], beta1=hp["beta1"], beta2=hp["beta2"],
                epsilon=hp["epsilon"], parameters=net,
                weight_decay=hp["weight_decay"]),
            loss=GPTFusedPretrainingCriterion(), amp_configs=tr["amp"])
        if mesh is not None:
            parallel.distributed_model(model, mesh=mesh)
        ctx.mark("weights")

        first = train_steps.batches(mix, ctx.seed, d["V"], n_first)
        norms = jax.jit(lambda t: {k: jnp.sqrt(jnp.sum(jnp.square(
            v.astype(jnp.float32)))) for k, v in t.items()})
        # the first steps, through the window's own object, call and feed
        probe = Steps()
        _fit(model, Batches(first[:1]), probe)
        m1 = jax.device_get(norms(model._opt_state["m"]))
        grad_norms = {k: float(v) / (1.0 - hp["beta1"])
                      for k, v in m1.items()}
        _fit(model, Batches(first[1:]), probe)
        words = weights.key_words(ctx.seed)
        change = jax.device_get(jax.jit(lambda p: {
            k: jnp.sqrt(jnp.sum(jnp.square(p[k] - v)))
            for k, v in weights.unstack(weights.make_stacked(
                d, words, jnp.float32), d).items()})(model._params))
        got = {"losses": list(probe.losses), "grad_norms": grad_norms,
               "change_norms": {k: float(v) for k, v in change.items()}}
        ctx.mark("warmup")

        # the window: the same model, one more call to fit, fed until the
        # deadline; a step counts when its loss reached the host inside
        per_step = probe.ends[-1] - probe.ends[-2]
        n_max = int(ctx.seconds / max(per_step, 1e-3) * 1.5) + 16
        data = train_steps.batches(mix, ctx.seed, d["V"],
                                   min(n_max, int(mix["max_steps"])),
                                   first=n_first)
        steps = Steps()
        compiles0 = ctx.compile_count()
        t0 = time.monotonic()
        t_end = t0 + ctx.seconds
        ctx.window_opens()
        if ctx.trace:
            ctx.trace_between(t0 + ctx.seconds * 0.3,
                              t0 + ctx.seconds * 0.3
                              + min(float(mix.get("trace_s", 5.0)),
                                    ctx.seconds * 0.5))
        _fit(model, Batches(data, t_end), steps)
        if ctx.trace:
            ctx.trace_join()
        compiled_inside = ctx.compile_count() - compiles0
        ctx.read_memory_peak()
    finally:
        if mesh is not None:
            parallel.set_mesh(None)

    # whole steps only: the rate is taken over the steps whose loss reached the
    # host inside the window and the time up to the last of them, so that it
    # does not jump by a step's worth with where the window happens to close
    inside = [t for t in steps.ends if t <= t_end]
    per_step = int(mix["batch"]) * int(mix["seq"])
    window_s = inside[-1] - t0 if inside else t_end - t0
    ctx.say({"steps": {"ended_inside": len(inside), "ran": len(steps.ends),
                       "tokens_a_step": per_step,
                       "seconds_to_the_last_of_them": window_s},
             "programs_compiled_inside_window": compiled_inside,
             "loss_first_last": [steps.losses[0], steps.losses[-1]]
             if steps.losses else None})
    e2e = {}
    if inside:
        e2e["train_tok_per_s"] = len(inside) * per_step / window_s
    # free the program's state before the reference runs: the callbacks and
    # the program's registries still point at the model, so drop the arrays
    # themselves
    probe.model = steps.model = None
    for tree in (model._params, model._frozen, model._buffers,
                 model._opt_state):
        for leaf in jax.tree_util.tree_leaves(tree):
            if hasattr(leaf, "delete"):
                leaf.delete()
    del model, net
    gc.collect()
    jax.clear_caches()
    gc.collect()

    check = check_trained(ctx, got, first, d, hp, ctx.check, steps.losses)
    facts = {"dims": d, "seq": int(mix["seq"]), "chips": ctx.chips,
             "window_s": window_s, "steps_inside": len(inside),
             "step_program": "step", "steps_per_execution": 1,
             "compiled_inside": compiled_inside,
             "train_tok_per_s": e2e.get("train_tok_per_s")}
    return {"attempted": len(steps.ends), "failed": 0, "end_to_end": e2e,
            "facts": facts,
            "correct": check["correct"] and len(inside) > 0}


def check_trained(ctx, got: dict, first, d: dict, hp: dict, spec: dict,
                  window_losses) -> dict:
    """Follow the same first steps with the float32 reference (the program's
    state is freed by now) and compare each step's loss, the first gradient's
    norm and the norm of the parameters' change, the norms by the worst
    leaf."""
    import math
    import jax
    import jax.numpy as jnp
    import numpy as np
    from ..reference import gpt_dense
    t_ref = time.monotonic()
    words = weights.key_words(ctx.seed)

    def make_p0():
        return weights.make_stacked(d, words, jnp.float32)

    def follow(quant):
        ref = gpt_dense.train_reference(
            make_p0, [jnp.asarray(b) for b in first], d, hp, quant)
        flat = {}
        for key in ("grad_norms", "change_norms"):
            flat[key] = {}
            for name, v in ref[key].items():
                v = np.asarray(v)
                if v.ndim:
                    for l in range(v.shape[0]):
                        flat[key][weights.program_name(name, l)] = float(v[l])
                else:
                    flat[key][name] = float(v)
        flat["losses"] = ref["losses"]
        return flat

    def compare(side, ref):
        return {"loss": max(stats.rel_gap(a, b) for a, b in
                            zip(side["losses"], ref["losses"])),
                "grad_norm": stats.worst_leaf_gap(side["grad_norms"],
                                                  ref["grad_norms"]),
                "change_norm": stats.worst_leaf_gap(side["change_norms"],
                                                    ref["change_norms"])}

    ref = follow(None)
    cmp_ = compare(got, ref)
    limits = spec["limits"]
    finite = all(math.isfinite(x) for x in window_losses)
    tail = sorted(window_losses[-10:])
    falling = bool(tail) and tail[len(tail) // 2] \
        < got["losses"][0] + limits.get("loss_rise", 0.0)
    line = {"check": "first steps against the float32 reference",
            "steps": len(first), "losses": got["losses"],
            "reference_losses": ref["losses"],
            "loss_rel_gap": cmp_["loss"], "loss_limit": limits["loss"],
            "grad_norm_gap": cmp_["grad_norm"],
            "grad_norm_limit": limits["grad_norm"],
            "change_norm_gap": cmp_["change_norm"],
            "change_norm_limit": limits["change_norm"],
            "window_loss_finite": finite, "window_loss_falling": falling}
    if ctx.control:
        ctl = compare(follow(spec["control"]), ref)
        line["control"] = {"quant": spec["control"], "loss_rel_gap":
                           ctl["loss"], "grad_norm_gap": ctl["grad_norm"],
                           "change_norm_gap": ctl["change_norm"]}
    line["reference_seconds"] = round(time.monotonic() - t_ref, 2)
    ctx.say(line)
    ok = (cmp_["loss"] <= limits["loss"]
          and cmp_["grad_norm"]["gap"] <= limits["grad_norm"]
          and cmp_["change_norm"]["gap"] <= limits["change_norm"]
          and finite and falling)
    return {"correct": bool(ok)}
