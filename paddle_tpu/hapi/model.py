"""paddle_tpu.Model — the Keras-style trainer.

Rebuild of the reference's high-level API
(reference: python/paddle/hapi/model.py — Model:915, fit:1574,
prepare:1499, evaluate:1709, predict:1791, train_batch:1055,
DynamicGraphAdapter.train_batch:704, StaticGraphAdapter:246).

TPU-native design: there is exactly one adapter. ``prepare`` builds a
jitted functional train step — params/optimizer-state/buffers live on
device across the whole fit loop (donated buffers, no per-step host
sync; the reference's dygraph adapter re-enters Python per op, its static
adapter pre-builds a Program — jit tracing gives us the static-graph
performance with the dygraph definition). Sharded training reuses this
exact class: ``parallel.DistributedModel`` supplies shardings and the
step compiles to an SPMD program.
"""

from __future__ import annotations

import base64
import contextlib
import os
import pickle
import sys
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import amp
from ..core import compile_cache, flags, rng
from ..io import DataLoader, Dataset
from ..metric import Metric
from ..nn.layer import Layer, functional_call, split_state
from ..observability import goodput as _goodput
from ..observability import memory as _memobs
from ..observability import metrics as _obs
from ..observability import perf as _perf
from ..observability import tracing as _trace
from ..optimizer.optimizer import Optimizer
from ..reliability import faults as _faults
from ..reliability import guard as _nguard
from ..reliability.faults import FaultInjected
from .callbacks import config_callbacks


def _train_metrics():
    """Training instruments in the process-wide registry. Step time is
    the dispatch wall time of the fused train step (the loss stays on
    device — no forced sync); the first step of each new input shape
    includes its XLA compile and is double-counted into the compile
    histogram so recompile storms are visible (VERDICT r5's MFU gap
    hunt starts here)."""
    reg = _obs.default_registry()
    return {
        "step": reg.histogram(
            "train_step_seconds",
            "train_batch dispatch wall time (loss left on device)"),
        "eps": reg.histogram(
            "train_examples_per_second",
            "batch size / step wall time", buckets=_obs.RATE_BUCKETS),
        "compile_count": reg.counter(
            "train_compile_count",
            "distinct input (shape, dtype) signatures = XLA compiles"),
        "compile": reg.histogram(
            "train_compile_seconds",
            "wall time of the first step for each new signature",
            buckets=(0.1, 0.5, 1.0, 5.0, 15.0, 30.0, 60.0, 120.0,
                     300.0, 600.0)),
        "steps": reg.gauge(
            "train_step_count", "optimizer steps taken this process"),
    }


def _loop_metrics():
    """Fused multi-step loop instruments: one slab = one XLA dispatch
    covering K optimizer steps (docs/OBSERVABILITY.md train_loop_*)."""
    reg = _obs.default_registry()
    return {
        "dispatch": reg.histogram(
            "train_loop_dispatch_seconds",
            "wall time of one fused K-step slab dispatch (losses and "
            "metrics stay on device)"),
        "slab": reg.histogram(
            "train_loop_slab_size",
            "optimizer steps fused into each dispatched slab",
            buckets=(1.0, 2.0, 4.0, 8.0, 16.0, 32.0, 64.0, 128.0)),
        "drain": reg.histogram(
            "train_loop_drain_seconds",
            "host time coercing buffered device metrics/losses at "
            "log_freq/epoch boundaries (the deferred sync)"),
    }


def _as_tuple(x):
    if isinstance(x, (list, tuple)):
        return tuple(x)
    return (x,)


def _shape_signature(inputs, labels) -> Tuple:
    """The (shape, dtype) tuple per input/label leaf that identifies
    one compiled program — built ONCE per step and shared by the
    recompile guard, the perf cost registry, and the guard's abort
    fingerprint (three consumers, one construction)."""
    return tuple(
        (tuple(np.shape(a)), str(getattr(a, "dtype", type(a))))
        for a in (*inputs, *labels))


class _FloatView:
    """Float-like lazy value: subclasses define __float__; comparisons,
    arithmetic and formatting all coerce through it, so log consumers
    that did math on the old plain-float entries keep working."""

    __slots__ = ()

    def __float__(self):  # pragma: no cover — abstract
        raise NotImplementedError

    def __format__(self, spec):
        return format(float(self), spec)

    def __repr__(self):
        return repr(float(self))

    def __bool__(self):
        return bool(float(self))

    def __eq__(self, other):
        return float(self) == other

    def __ne__(self, other):
        return float(self) != other

    def __lt__(self, other):
        return float(self) < other

    def __le__(self, other):
        return float(self) <= other

    def __gt__(self, other):
        return float(self) > other

    def __ge__(self, other):
        return float(self) >= other

    def __hash__(self):
        return hash(float(self))

    def __add__(self, other):
        return float(self) + other

    __radd__ = __add__

    def __sub__(self, other):
        return float(self) - other

    def __rsub__(self, other):
        return other - float(self)

    def __mul__(self, other):
        return float(self) * other

    __rmul__ = __mul__

    def __truediv__(self, other):
        return float(self) / other

    def __rtruediv__(self, other):
        return other / float(self)

    def __neg__(self):
        return -float(self)

    def __abs__(self):
        return abs(float(self))

    def __int__(self):
        return int(float(self))

    def __round__(self, ndigits=None):
        return round(float(self), ndigits)

    def __trunc__(self):
        import math
        return math.trunc(float(self))


class _SlabScalar(_FloatView):
    """One step's loss inside a [K]-stacked device array — indexing and
    host coercion happen only if the value is actually read (display,
    CSV, bench sync), so the fused loop's K losses cost zero syncs when
    nobody looks."""

    __slots__ = ("_arr", "_idx")

    def __init__(self, arr, idx: int):
        self._arr = arr
        self._idx = idx

    def __float__(self):
        return float(self._arr[self._idx])

    def __array__(self, dtype=None):
        out = np.asarray(np.asarray(self._arr)[self._idx])
        return out.astype(dtype) if dtype is not None else out


class _LazyMetricValue(_FloatView):
    """Deferred metric read: Model.train_batch/train_loop_batch buffer
    device-resident ``Metric.compute`` outputs instead of coercing them
    per step; reading this value (float()/display/comparison) drains
    the buffer into the metric accumulators — one host sync per log
    boundary, not per optimizer step. The first read memoizes, so a log
    value coerced at its display boundary stays correct even if the
    metric is later reset (eval pass / next epoch); values NEVER read
    before a reset reflect the post-reset accumulator."""

    __slots__ = ("_model", "_metric", "_idx", "_val")

    def __init__(self, model, metric, idx: int):
        self._model = model
        self._metric = metric
        self._idx = idx
        self._val = None

    def __float__(self):
        if self._val is None:
            self._model._drain_metric_updates()
            res = self._metric.accumulate()
            res = res if isinstance(res, (list, tuple)) else [res]
            self._val = float(res[self._idx])
        return self._val


class Model:
    """ref: python/paddle/hapi/model.py:915."""

    def __init__(self, network: Layer, inputs=None, labels=None):
        self.network = network
        self._inputs_spec = inputs
        self._labels_spec = labels
        self._optimizer: Optional[Optimizer] = None
        self._loss = None
        self._metrics: List[Metric] = []
        self.stop_training = False
        # device-resident training state
        self._params = None
        self._frozen = None
        self._buffers = None
        self._opt_state = None
        self._step_count = 0
        self._train_step_fn = None
        self._train_loop_fn = None    # fused K-step scan (steps_per_loop)
        self._eval_step_fn = None
        self._predict_fn = None
        # sharding hooks (set by parallel.DistributedModel)
        self._shard_params = None     # fn(params) -> sharded params
        self._shard_batch = None      # fn(batch) -> sharded batch
        self._shard_superbatch = None  # fn([K,...] slab) -> sharded slab
        # recompile guard: distinct (shape, dtype) signatures seen
        self._shape_signatures = set()
        # device metric outputs buffered until a log/display boundary
        # coerces them (_drain_metric_updates) — no per-step host sync
        self._metric_pending: List[Tuple[Tuple, int]] = []
        # numeric guard (reliability/guard.py): policy armed at
        # prepare(); verdicts/grad-norms/losses buffered per dispatch
        # and drained with the metrics (zero extra host syncs). The
        # legacy check_nan_inf flag buffers its losses the same way.
        self._guard: Optional["_nguard.GuardPolicy"] = None
        self._guard_state = None
        self._guard_pending: List[Tuple] = []
        self._nan_pending: List[Tuple] = []
        self._last_batch_shapes = None
        # observability handles, created lazily on the first step
        self._obs = None
        self._obs_loop = None
        # perf cost registry handles (observability/perf.py): one per
        # compiled train-step/loop signature, keyed by the same shape
        # tuples _guard_recompiles tracks (same 4096-cap discipline);
        # the scope token keeps this Model's programs distinct from
        # any other owner's in the process-wide registry
        self._reset_perf_scope()
        # memory-ledger scope (observability/memory.py): params /
        # opt-state / buffers bytes registered per-dtype when the
        # device trees are built (same reset-on-reprepare discipline
        # as the perf scope — stale rows must not survive a rebuild)
        self._reset_mem_scope()

    # -- preparation --------------------------------------------------------
    def prepare(self, optimizer: Optional[Optimizer] = None, loss=None,
                metrics: Optional[Sequence[Metric]] = None,
                amp_configs=None, numeric_guard=None) -> None:
        """ref: hapi/model.py:1499.

        ``numeric_guard``: a :class:`reliability.guard.GuardPolicy`
        (or ``True`` for the defaults) arms the on-device numeric
        guard — finite-mask over loss/grads, global grad norm, and
        loss-spike EMA computed INSIDE the jitted step, tripped steps
        device-masked to exact no-op updates, verdicts drained with
        the buffered metrics. ``None`` falls back to the
        ``numeric_guard`` flag; disabled costs one attribute check
        per train call and zero ops in the compiled program."""
        self._optimizer = optimizer
        self._loss = loss
        if metrics is None:
            metrics = []
        elif isinstance(metrics, Metric):  # single metric (ref: to_list)
            metrics = [metrics]
        self._metrics = list(metrics)
        self._amp_configs = amp_configs
        if numeric_guard is None and flags.get_flag("numeric_guard"):
            numeric_guard = True
        if numeric_guard is True:
            numeric_guard = _nguard.GuardPolicy()
        self._guard = numeric_guard or None
        self._guard_state = None
        self._guard_pending.clear()
        self._nan_pending.clear()
        self._train_step_fn = None
        self._train_loop_fn = None
        self._eval_step_fn = None
        self._predict_fn = None
        self._metric_pending.clear()
        # re-prepare rebuilds the compiled programs (optimizer/loss/
        # metrics changed → different FLOPs): stale perf handles would
        # attribute the NEW program's dispatches to the OLD program's
        # cached cost analysis, and the dead entries would leak toward
        # PROGRAM_CAP
        self._reset_perf_scope()
        # fresh ledger rows: the opt-state tree this prepare implies
        # may differ (AdamW -> Adafactor is a 3 orders-of-magnitude
        # accounting change); register what exists NOW (the network's
        # param/buffer trees), and again with the optimizer state when
        # _sync_state_in builds the device trees
        self._reset_mem_scope()
        if _memobs.enabled():
            self._register_memory()
        compile_cache.enable()
        self._register_status_provider()

    def _register_status_provider(self) -> None:
        """Expose train-loop state on the debug server's /statusz
        (weakref closure — a collected Model drops out of the
        listing). Idempotent per Model: prepare() re-registers under
        the same name."""
        import weakref
        from ..observability import server as _dbgsrv
        ref = weakref.ref(self)

        def _status():
            m = ref()
            if m is None:
                return None
            out = {
                "step_count": m._step_count,
                "compiled_shapes": m.compiled_shape_count,
                "pending_metric_buffers": len(m._metric_pending),
                "loop_compiled": m._train_loop_fn is not None,
                "step_compiled": m._train_step_fn is not None,
                "stop_training": m.stop_training,
            }
            if m._guard is not None:
                out["numeric_guard"] = m._guard.status()
            return out

        _dbgsrv.register_status_provider(
            f"train_model_{id(self):x}", _status)

    def _sync_state_in(self):
        """Pull state out of the stateful network into device trees.
        Only trainable params are differentiated/updated; frozen ones
        (Parameter(trainable=False)) ride along as constants."""
        built = False
        if self._params is None:
            params, buffers = split_state(self.network)
            meta = self.network.param_meta()
            trainable = {k: v for k, v in params.items()
                         if meta[k].trainable}
            frozen = {k: v for k, v in params.items()
                      if not meta[k].trainable}
            if self._shard_params is not None:
                trainable = self._shard_params(trainable)
                frozen = self._shard_params(frozen)
                buffers = self._shard_params(buffers)
            self._params = dict(trainable)
            self._frozen = dict(frozen)
            self._buffers = dict(buffers)
            if self._shard_params is not None:
                # the network still holds the UNSHARDED arrays it was
                # built with, all on the first device: rebind it to the
                # sharded trees so that copy is freed (a full f32 model
                # on one chip of the mesh otherwise)
                self._sync_state_out()
            built = True
        if self._opt_state is None and self._optimizer is not None:
            self._opt_state = self._optimizer.init_state(self._params)
            built = True
        if built and _memobs.enabled():
            # allocation boundary: the device trees (and now the
            # opt-state tree) exist — re-register the per-dtype rows
            # under the same scope keys (overwrite, never accumulate)
            self._register_memory()

    def sync_weights(self):
        """Rebind the latest device state onto the network's attributes.

        The compiled train step donates its inputs, so after
        ``train_batch`` the arrays previously bound to the network are
        deleted; touching the network directly (``net(x)``,
        ``net.generate(...)``, ``net.state_dict()``) then raises
        "Array has been deleted". ``fit``/``save``/checkpointing sync
        automatically; manual ``train_batch`` loops call this before
        using the network object. Cost is reference rebinding only —
        the arrays stay on device. (ref: the reference's dygraph Model
        shares parameter objects with the network, so this hazard
        doesn't exist there; donation is the TPU-side trade for
        in-place optimizer updates.)"""
        self._sync_state_out()

    def _sync_state_out(self):
        """Write device state back into the network (on save/exit)."""
        if self._params is not None:
            for name, v in self._params.items():
                self.network._assign_by_path(name, v)
        if getattr(self, "_frozen", None):
            for name, v in self._frozen.items():
                self.network._assign_by_path(name, v)
        if self._buffers is not None:
            for name, v in self._buffers.items():
                self.network._assign_by_path(name, v)

    def _compute_loss(self, outputs, labels):
        loss_fn = self._loss
        outs = _as_tuple(outputs)
        labs = _as_tuple(labels)
        with jax.named_scope("loss"):
            return loss_fn(*outs, *labs)

    def _metric_outputs(self, outputs, labels):
        outs = _as_tuple(outputs)
        labs = _as_tuple(labels)
        return tuple(m.compute(outs[0], labs[0]) for m in self._metrics)

    def _amp_context(self):
        """amp_configs from prepare() → auto_cast context entered at trace
        time (ref: hapi/model.py _init_amp + amp/auto_cast.py). Accepts a
        level string ("O1"/"O2") or a dict {level, dtype, ...}."""
        cfg = self._amp_configs
        if not cfg:
            return contextlib.nullcontext()
        if isinstance(cfg, str):
            cfg = {"level": cfg}
        level = cfg.get("level", "O1")
        if level == "O0":
            return contextlib.nullcontext()
        return amp.auto_cast(
            enable=True, dtype=cfg.get("dtype"), level=level,
            custom_white_list=cfg.get("custom_white_list"),
            custom_black_list=cfg.get("custom_black_list"))

    # -- compiled steps -----------------------------------------------------
    def _build_train_step(self):
        optimizer = self._optimizer
        guard = self._guard

        if guard is not None:
            mask_spikes = guard.mask_spikes  # static at trace time

            def gstep(params, frozen, opt_state, buffers, gstate,
                      step_idx, key, inputs, labels, poison):
                def loss_fn(p):
                    with rng.key_guard(key), self._amp_context():
                        out, new_buf = functional_call(
                            self.network, {**p, **frozen}, buffers,
                            *inputs, training=True)
                    loss = self._compute_loss(out, labels)
                    # poison: 1.0 (bit-exact identity) or NaN — the
                    # grad.nonfinite injection point, an input so the
                    # schedule never retraces
                    return loss.astype(jnp.float32) * poison, \
                        (out, new_buf)
                (loss, (out, new_buf)), grads = jax.value_and_grad(
                    loss_fn, has_aux=True)(params)
                verdict, gnorm = guard.inspect(loss, grads, gstate)
                ok = _nguard.apply_mask(verdict, mask_spikes)
                new_params, new_opt = optimizer.apply_gradients(
                    params, grads, opt_state, step_idx)
                # tripped step → EXACT no-op update: params, optimizer
                # moments/counters and buffers all keep their pre-step
                # bits (jnp.where select per leaf)
                new_params = _nguard.mask_pytree(ok, new_params, params)
                new_opt = _nguard.mask_pytree(ok, new_opt, opt_state)
                new_buf = _nguard.mask_pytree(ok, dict(new_buf), buffers)
                new_gstate = guard.update_state(gstate, loss, ok)
                metric_outs = self._metric_outputs(out, labels)
                return (loss, new_params, new_opt, new_buf, new_gstate,
                        (verdict, gnorm), metric_outs)

            donate = (0, 2, 3, 4) if flags.get_flag("donate_buffers") \
                else ()
            return jax.jit(gstep, donate_argnums=donate)

        def step(params, frozen, opt_state, buffers, step_idx, key,
                 inputs, labels):
            def loss_fn(p):
                with rng.key_guard(key), self._amp_context():
                    out, new_buf = functional_call(
                        self.network, {**p, **frozen}, buffers, *inputs,
                        training=True)
                loss = self._compute_loss(out, labels)
                return loss.astype(jnp.float32), (out, new_buf)
            (loss, (out, new_buf)), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params)
            new_params, new_opt = optimizer.apply_gradients(
                params, grads, opt_state, step_idx)
            metric_outs = self._metric_outputs(out, labels)
            return loss, new_params, new_opt, new_buf, metric_outs

        donate = (0, 2, 3) if flags.get_flag("donate_buffers") else ()
        return jax.jit(step, donate_argnums=donate)

    def _build_train_loop(self):
        """Fused multi-step train loop: ONE jitted program running a
        lax.scan over the leading (steps) dim of a [K, batch, ...]
        superbatch. Params/opt-state/buffers are carried and donated
        across the whole slab — one Python→XLA dispatch per K optimizer
        steps instead of per step. Each scan iteration derives its key
        as ``fold_in(base_key, step_idx)``, exactly what
        ``rng.split_for_step`` computes on the K=1 path, so the loss
        stream is bit-identical to K separate train_batch calls
        (pinned by tests/test_train_loop.py for the dense/transformer
        family incl. AMP + dropout + fused vocab loss; conv backward
        passes may reassociate one reduction differently between the
        scanned and straight-line programs on XLA:CPU — ≤1 ULP/step).
        Per-step losses and metric outputs come back stacked [K, ...]
        and stay on device.

        With the numeric guard armed, each scan iteration additionally
        computes its verdict/grad-norm on device and masks the carry
        update (``jnp.where`` per leaf) when tripped — a poisoned step
        inside the slab becomes an exact no-op and CANNOT corrupt the
        K-1 steps after it, while the slab stays one dispatch.
        Verdicts come back stacked [K] and drain with the metrics."""
        optimizer = self._optimizer
        guard = self._guard

        if guard is not None:
            mask_spikes = guard.mask_spikes

            def gloop(params, frozen, opt_state, buffers, gstate,
                      step0, base_key, inputs, labels, poison):
                def body(carry, xs):
                    p, opt_st, buf, gs = carry
                    idx, pois, inp, lab = xs
                    step_idx = step0 + idx

                    def loss_fn(pp):
                        with rng.key_guard(jax.random.fold_in(
                                base_key, step_idx)), \
                                self._amp_context():
                            out, new_buf = functional_call(
                                self.network, {**pp, **frozen}, buf,
                                *inp, training=True)
                        loss = self._compute_loss(out, lab)
                        return loss.astype(jnp.float32) * pois, \
                            (out, new_buf)

                    (loss, (out, new_buf)), grads = jax.value_and_grad(
                        loss_fn, has_aux=True)(p)
                    verdict, gnorm = guard.inspect(loss, grads, gs)
                    ok = _nguard.apply_mask(verdict, mask_spikes)
                    new_p, new_opt = optimizer.apply_gradients(
                        p, grads, opt_st, step_idx)
                    new_p = _nguard.mask_pytree(ok, new_p, p)
                    new_opt = _nguard.mask_pytree(ok, new_opt, opt_st)
                    new_buf = _nguard.mask_pytree(ok, dict(new_buf),
                                                  buf)
                    new_gs = guard.update_state(gs, loss, ok)
                    metric_outs = self._metric_outputs(out, lab)
                    return (new_p, new_opt, new_buf, new_gs), \
                        (loss, verdict, gnorm, metric_outs)

                k = jax.tree_util.tree_leaves(
                    (inputs, labels))[0].shape[0]
                (params, opt_state, buffers, gstate), \
                    (losses, verdicts, gnorms, metric_outs) = \
                    jax.lax.scan(
                        body, (params, opt_state, buffers, gstate),
                        (jnp.arange(k), poison, inputs, labels))
                return (losses, params, opt_state, buffers, gstate,
                        (verdicts, gnorms), metric_outs)

            donate = (0, 2, 3, 4) if flags.get_flag("donate_buffers") \
                else ()
            return jax.jit(gloop, donate_argnums=donate)

        def loop(params, frozen, opt_state, buffers, step0, base_key,
                 inputs, labels):
            def body(carry, xs):
                p, opt_st, buf = carry
                idx, inp, lab = xs
                step_idx = step0 + idx

                def loss_fn(pp):
                    with rng.key_guard(jax.random.fold_in(
                            base_key, step_idx)), self._amp_context():
                        out, new_buf = functional_call(
                            self.network, {**pp, **frozen}, buf, *inp,
                            training=True)
                    loss = self._compute_loss(out, lab)
                    return loss.astype(jnp.float32), (out, new_buf)

                (loss, (out, new_buf)), grads = jax.value_and_grad(
                    loss_fn, has_aux=True)(p)
                new_p, new_opt = optimizer.apply_gradients(
                    p, grads, opt_st, step_idx)
                metric_outs = self._metric_outputs(out, lab)
                # functional_call returns an OrderedDict; the scan carry
                # must keep the input's plain-dict pytree type
                return (new_p, new_opt, dict(new_buf)), (loss, metric_outs)

            k = jax.tree_util.tree_leaves((inputs, labels))[0].shape[0]
            (params, opt_state, buffers), (losses, metric_outs) = \
                jax.lax.scan(body, (params, opt_state, buffers),
                             (jnp.arange(k), inputs, labels))
            return losses, params, opt_state, buffers, metric_outs

        donate = (0, 2, 3) if flags.get_flag("donate_buffers") else ()
        return jax.jit(loop, donate_argnums=donate)

    def _build_eval_step(self):
        def step(params, frozen, buffers, key, inputs, labels):
            with rng.key_guard(key), self._amp_context():
                out, _ = functional_call(
                    self.network, {**params, **frozen}, buffers, *inputs,
                    training=False)
            loss = self._compute_loss(out, labels) if self._loss else None
            metric_outs = self._metric_outputs(out, labels)
            return loss, metric_outs
        return jax.jit(step)

    def _build_predict_step(self):
        def step(params, frozen, buffers, inputs):
            out, _ = functional_call(
                self.network, {**params, **frozen}, buffers, *inputs,
                training=False)
            return out
        return jax.jit(step)

    def _split_batch(self, batch) -> Tuple[Tuple, Tuple]:
        batch = _as_tuple(batch)
        if len(batch) == 1:
            return batch, ()
        n_labels = len(self._labels_spec) if self._labels_spec else 1
        return batch[:-n_labels], batch[-n_labels:]

    @property
    def compiled_shape_count(self) -> int:
        """Distinct input (shape, dtype) signatures the train/eval steps
        have seen — each one is a separate XLA compile (the quantity the
        recompile guard and io.sequence bucketing bound)."""
        return len(self._shape_signatures)

    def _guard_recompiles(self, inputs, labels, kind: str = "step",
                          sig_items: Optional[Tuple] = None) -> bool:
        """Every distinct input shape recompiles the jitted step (XLA
        static shapes — SURVEY §7 hard parts). Track the signatures seen
        and warn once past FLAGS.recompile_warn_threshold, pointing at
        the padding/bucketing tools (io.sequence). Returns True when
        this batch introduces a NEW signature (= a compile is coming),
        which train_batch routes into the compile-time histogram.
        ``kind`` separates the per-batch step from the fused K-step loop
        ("loop"): a [K, b, ...] superbatch is its own program, one
        signature per distinct superbatch shape, counted in the same
        bounded set as K=1 signatures. Threshold 0 keeps its meaning as
        the full off switch (no tracking, no warning — intentionally-
        dynamic workloads opt out of the per-batch signature cost;
        compile metrics read 0), and the signature set is capped so a
        long dynamic run can't grow host memory without bound."""
        thresh = flags.get_flag("recompile_warn_threshold")
        if not thresh:
            return False
        seen = self._shape_signatures
        if len(seen) >= 4096:
            return False
        if sig_items is None:
            sig_items = _shape_signature(inputs, labels)
        sig = (kind,) + sig_items
        if sig in seen:
            return False
        seen.add(sig)
        if len(seen) == thresh + 1:
            import warnings
            warnings.warn(
                f"Model step has now seen {len(seen)} distinct input "
                f"shapes; each one is a full XLA recompile. Pad or "
                f"bucket variable-length data (io.sequence.pad_sequence "
                f"/ LengthBucketBatchSampler), or raise "
                f"FLAGS.recompile_warn_threshold if intentional.",
                stacklevel=3)
        return True

    def _reset_perf_scope(self) -> None:
        """Fresh perf-registry scope + GC finalizer — ``__init__`` and
        every re-prepare share this one block: the old scope's entries
        are released (a discarded/re-prepared Model must not leak
        toward PROGRAM_CAP or keep stale cost entries), and the
        finalizer backstops Models dropped without either path."""
        old = getattr(self, "_perf_scope", None)
        if old is not None:
            if self._perf_programs:
                _perf.instance().remove_scope(old)
            self._perf_finalizer.detach()
        self._perf_programs = {}
        self._perf_scope = _perf.next_scope()
        self._perf_finalizer = _perf.finalize_scope(
            self, self._perf_scope)

    def _reset_mem_scope(self) -> None:
        """Fresh memory-ledger scope + GC finalizer (the perf-scope
        discipline): a re-prepared/discarded Model's rows are
        released, and the finalizer backstops Models dropped without
        either path."""
        old = getattr(self, "_mem_scope", None)
        if old is not None:
            _memobs.instance().remove_scope(old)
            self._mem_finalizer.detach()
        self._mem_scope = _memobs.next_scope()
        self._mem_finalizer = _memobs.finalize_scope(
            self, self._mem_scope)

    def _register_memory(self) -> None:
        """Register this Model's attributed reservations: params (the
        trainable + frozen trees), buffers, and — once built —
        optimizer state, per dtype, bytes from the ABSTRACT tree
        (shape x itemsize; no device sync, no buffer retained).
        Idempotent per scope: re-registration overwrites the same
        (owner, kind) rows, so prepare-then-train registers twice and
        the second write adds the opt-state rows the first couldn't
        know."""
        if self._params is not None:
            params = dict(self._params)
            params.update(self._frozen or {})
            buffers = self._buffers or {}
        else:
            params, buffers = split_state(self.network)
        trees = {"train_params": params, "train_buffers": buffers}
        if self._opt_state is not None:
            trees["train_opt_state"] = self._opt_state
        led = _memobs.instance()
        for owner, tree in trees.items():
            for dt, nb in _memobs.tree_bytes_by_dtype(tree).items():
                led.set_entry(self._mem_scope, owner, dt, nb)

    def _perf_program(self, kind: str, sig_items: Tuple, fn, args,
                      steps: int):
        """(handle, fresh) for this (kind, input-signature) compiled
        program in the perf cost registry (observability/perf.py).
        Registration — once per signature — converts ``args`` to an
        ABSTRACT signature immediately (no device buffers retained
        past the donating call) for the one-time XLA cost-analysis
        lowering. ``fresh`` is True the first time perf sees the
        signature (= a compile is coming), tracked HERE so compile
        attribution stays correct even when the recompile-warning
        guard is opted out (FLAGS.recompile_warn_threshold=0).
        Steady state is a dict hit; callers gate the whole path on
        ``_perf.enabled()`` (one flag check when disabled)."""
        key = (kind,) + sig_items
        if key in self._perf_programs:
            return self._perf_programs[key], False
        if len(self._perf_programs) >= _perf.PROGRAM_CAP:
            return None, False
        h = _perf.register_program(
            "train", kind, sig=sig_items,
            lower=_perf.make_lower(fn, args), steps=steps,
            scope=self._perf_scope)
        self._perf_programs[key] = h
        return h, True

    # -- numeric-guard plumbing ---------------------------------------------
    def _maybe_poison_batch(self, inputs, k: int):
        """Injection site ``data.poison``: one check per optimizer
        step about to dispatch. A hit NaN-poisons the step's FLOAT
        input leaves (host-side, before device_put) instead of
        raising — models a corrupt record/decoder bug riding the data
        stream. Only reached while chaos is armed."""
        bad = []
        for i in range(k):
            try:
                _faults.check("data.poison")
            except FaultInjected:
                bad.append(i)
        if not bad:
            return inputs

        def poison(x):
            a = np.array(np.asarray(x), copy=True)
            if np.issubdtype(a.dtype, np.floating):
                if k == 1:
                    a[...] = np.nan
                else:
                    a[bad] = np.nan
            return a

        return jax.tree_util.tree_map(poison, inputs)

    def _grad_poison(self, k: int):
        """Injection site ``grad.nonfinite``: the per-step loss
        multiplier fed into the guarded program — 1.0 (bit-exact
        identity) normally, NaN on schedule, so loss AND grads go
        non-finite inside the compiled step without retracing."""
        vec = np.ones((k,), np.float32)
        if _faults.enabled():
            for i in range(k):
                try:
                    _faults.check("grad.nonfinite")
                except FaultInjected:
                    vec[i] = np.nan
        # always [k]-shaped: the scanned loop feeds it as an xs leaf,
        # which needs the leading axis even at k=1 (train_batch's
        # per-step program indexes out its scalar)
        return vec

    def _buffer_guard_outs(self, verdicts, gnorms, losses,
                           step0: int, k: int) -> None:
        self._guard_pending.append((verdicts, gnorms, losses, step0, k))
        if len(self._guard_pending) >= self._PENDING_DRAIN_CAP:
            self._drain_metric_updates()

    def _buffer_nan_check(self, losses, step0: int, k: int) -> None:
        """The legacy ``check_nan_inf`` flag, deferred: buffer the
        device loss and test it at the next drain boundary — one host
        sync per log boundary instead of the old per-step
        ``np.isfinite`` stall, and the K>1 report names the exact
        in-slab step, not just the slab end."""
        self._nan_pending.append((losses, step0, k))
        if len(self._nan_pending) >= self._PENDING_DRAIN_CAP:
            self._drain_metric_updates()

    def _drain_guard_checks(self) -> None:
        """Coerce buffered guard verdicts / nan-check losses and apply
        policy. Runs inside the one metric-drain sync; may raise
        GuardRollback/GuardAbort (guard) or FloatingPointError
        (check_nan_inf)."""
        if self._nan_pending:
            pending, self._nan_pending = self._nan_pending, []
            for losses, step0, k in pending:
                arr = np.asarray(losses).reshape(-1)
                finite = np.isfinite(arr)
                if not finite.all():
                    idx = int(np.argmin(finite))
                    from ..amp.debugging import find_nonfinite
                    bad = find_nonfinite({"param": self._params,
                                          "buffer": self._buffers})
                    raise FloatingPointError(
                        f"NaN/Inf loss at step {step0 + idx}"
                        + (f" (step {idx} of a {k}-step slab)"
                           if k > 1 else "")
                        + f"; non-finite tensors: "
                          f"{bad or ['(loss only)']}")
        if self._guard_pending:
            pending, self._guard_pending = self._guard_pending, []
            for verdicts, gnorms, losses, step0, _k in pending:
                self._guard.process(verdicts, gnorms, losses, step0,
                                    model=self)

    # -- batch-level API ----------------------------------------------------
    def train_batch(self, inputs, labels=None) -> Dict[str, Any]:
        """ref: hapi/model.py:1055."""
        self._sync_state_in()
        if self._train_step_fn is None:
            self._train_step_fn = self._build_train_step()
        inputs = _as_tuple(inputs)
        labels = _as_tuple(labels) if labels is not None else ()
        if _faults.enabled():
            inputs = self._maybe_poison_batch(inputs, 1)
        # one signature build serves the recompile guard, the perf
        # registry, and the guard fingerprint; None when every
        # consumer is off
        sig_items = _shape_signature(inputs, labels) \
            if (_perf.enabled() or self._guard is not None
                or flags.get_flag("recompile_warn_threshold")) else None
        fresh_shape = self._guard_recompiles(inputs, labels,
                                             sig_items=sig_items)
        if self._obs is None:
            self._obs = _train_metrics()
        batch_n = np.shape(inputs[0])[0] if inputs and np.ndim(
            inputs[0]) else 0
        if self._guard is not None:
            # abort-fingerprint capture: guard-armed runs only — the
            # disabled path stays one attribute check
            self._last_batch_shapes = list(sig_items)
        sp = _trace.start_span(
            "train.step", attrs={"batch": batch_n,
                                 "step": self._step_count}) \
            if _trace.active() else None
        t0 = time.perf_counter()
        perf_h, perf_fresh = None, False
        try:
            if self._shard_batch is not None:
                inputs = self._shard_batch(inputs)
                labels = self._shard_batch(labels)
            key = rng.split_for_step(self._step_count)
            if self._guard is not None:
                if self._guard_state is None:
                    self._guard_state = self._guard.device_state()
                call_args = (self._params, self._frozen,
                             self._opt_state, dict(self._buffers),
                             self._guard_state, self._step_count, key,
                             inputs, labels, self._grad_poison(1)[0])
                if _perf.enabled():
                    perf_h, perf_fresh = self._perf_program(
                        "step", sig_items, self._train_step_fn,
                        call_args, 1)
                loss, self._params, self._opt_state, self._buffers, \
                    self._guard_state, (verdict, gnorm), metric_outs = \
                    self._train_step_fn(*call_args)
            else:
                call_args = (self._params, self._frozen,
                             self._opt_state, self._buffers,
                             self._step_count, key, inputs, labels)
                if _perf.enabled():
                    perf_h, perf_fresh = self._perf_program(
                        "step", sig_items, self._train_step_fn,
                        call_args, 1)
                loss, self._params, self._opt_state, self._buffers, \
                    metric_outs = self._train_step_fn(*call_args)
        except BaseException as e:
            # a caught-and-skipped bad batch must not leak a live span
            # (the _live registry is uncapped, unlike the finished ring)
            if sp is not None:
                sp.set_status("error")
                sp.end()
            # RESOURCE_EXHAUSTED: flight-dump the memory ledger's
            # per-owner table before the error unwinds (one-shot)
            _memobs.maybe_dump_oom(e, component="train")
            raise
        self._step_count += 1
        dt = time.perf_counter() - t0
        self._obs["step"].observe(dt)
        if _perf.enabled():
            # the SAME dt the histogram observes feeds the roofline
            # registry — no extra clocks, no host syncs. Compile steps
            # (perf_fresh: first sight of this signature, tracked
            # independently of the recompile-warning opt-out) go to
            # their own phase and are excluded from the program's MFU
            # accounting (a compile is not a dispatch).
            compiling = fresh_shape or perf_fresh
            _perf.record_phase(
                "train", "compile" if compiling else "dispatch", dt)
            if perf_h is not None and not compiling:
                perf_h.record(dt)
        if _goodput.enabled():
            # the time ledger rides the SAME dt: a fresh-signature
            # step waited on its XLA compile; any other interval is
            # device compute (productive)
            _goodput.note("compile" if (fresh_shape or perf_fresh)
                          else "productive", dt)
        if fresh_shape:
            self._obs["compile_count"].inc()
            self._obs["compile"].observe(dt)
        if sp is not None:
            if fresh_shape:
                sp.add_event("recompile", {"signature_count": len(
                    self._shape_signatures)})
            sp.end()
        if batch_n and dt > 0:
            self._obs["eps"].observe(batch_n / dt)
        self._obs["steps"].set(self._step_count)
        # keep the loss AND metric outputs on device — no per-step host
        # sync (the reference's dygraph adapter also returns without
        # waiting; a float()/np.asarray here would serialize every step
        # on the device stream). Metric outputs are buffered and drained
        # into the host accumulators only when a callback/display
        # actually coerces a value (log_freq/epoch boundaries); the
        # guard verdicts and the legacy check_nan_inf loss test ride
        # the same drain.
        logs = {"loss": loss}
        if self._guard is not None:
            self._buffer_guard_outs(verdict, gnorm, loss,
                                    self._step_count - 1, 1)
            self._buffer_metric_outs(metric_outs, 1, verdicts=verdict)
        else:
            if flags.get_flag("check_nan_inf"):
                self._buffer_nan_check(loss, self._step_count - 1, 1)
            self._buffer_metric_outs(metric_outs, 1)
        self._attach_metric_logs(logs)
        return logs

    def train_loop_batch(self, inputs, labels=None) -> List[Dict[str, Any]]:
        """Run ONE fused slab of K optimizer steps (K = leading dim of
        every input/label leaf, stacked [K, batch, ...] — see
        ``DataLoader.superbatches``). Dispatches a single scanned XLA
        program (``_build_train_loop``) and returns K per-step log
        dicts whose losses/metrics are lazy device views; the loss
        stream is bit-identical to K ``train_batch`` calls."""
        self._sync_state_in()
        if self._train_loop_fn is None:
            self._train_loop_fn = self._build_train_loop()
        inputs = _as_tuple(inputs)
        labels = _as_tuple(labels) if labels is not None else ()
        k = int(np.shape(inputs[0])[0])
        if _faults.enabled():
            inputs = self._maybe_poison_batch(inputs, k)
        sig_items = _shape_signature(inputs, labels) \
            if (_perf.enabled() or self._guard is not None
                or flags.get_flag("recompile_warn_threshold")) else None
        fresh_shape = self._guard_recompiles(inputs, labels,
                                             kind="loop",
                                             sig_items=sig_items)
        if self._obs is None:
            self._obs = _train_metrics()
        if self._obs_loop is None:
            self._obs_loop = _loop_metrics()
        batch_n = np.shape(inputs[0])[1] if np.ndim(inputs[0]) > 1 else 0
        if self._guard is not None:
            self._last_batch_shapes = list(sig_items)
        sp = _trace.start_span(
            "train.dispatch", attrs={"k": k, "batch": batch_n,
                                     "step0": self._step_count}) \
            if _trace.active() else None
        t0 = time.perf_counter()
        perf_h, perf_fresh = None, False
        try:
            if self._shard_superbatch is not None:
                inputs = self._shard_superbatch(inputs)
                labels = self._shard_superbatch(labels)
            base_key = rng.get_global_stream()._key
            if self._guard is not None:
                if self._guard_state is None:
                    self._guard_state = self._guard.device_state()
                call_args = (self._params, self._frozen,
                             self._opt_state, dict(self._buffers),
                             self._guard_state, self._step_count,
                             base_key, inputs, labels,
                             self._grad_poison(k))
                if _perf.enabled():
                    perf_h, perf_fresh = self._perf_program(
                        "loop", sig_items, self._train_loop_fn,
                        call_args, k)
                losses, self._params, self._opt_state, self._buffers, \
                    self._guard_state, (verdicts, gnorms), metric_outs \
                    = self._train_loop_fn(*call_args)
            else:
                # plain dict buffers: the per-step path may have left
                # an OrderedDict here, and the scan carry's pytree
                # type must match the body's output (a plain dict)
                call_args = (self._params, self._frozen,
                             self._opt_state, dict(self._buffers),
                             self._step_count, base_key, inputs,
                             labels)
                if _perf.enabled():
                    perf_h, perf_fresh = self._perf_program(
                        "loop", sig_items, self._train_loop_fn,
                        call_args, k)
                losses, self._params, self._opt_state, self._buffers, \
                    metric_outs = self._train_loop_fn(*call_args)
        except BaseException as e:
            if sp is not None:
                sp.set_status("error")
                sp.end()
            _memobs.maybe_dump_oom(e, component="train")
            raise
        self._step_count += k
        dt = time.perf_counter() - t0
        self._obs_loop["dispatch"].observe(dt)
        self._obs_loop["slab"].observe(k)
        self._obs["step"].observe(dt / k)
        if _perf.enabled():
            compiling = fresh_shape or perf_fresh
            _perf.record_phase(
                "train", "compile" if compiling else "dispatch", dt)
            if perf_h is not None and not compiling:
                perf_h.record(dt)
        if _goodput.enabled():
            # the time ledger rides the SAME dt: a fresh-signature
            # step waited on its XLA compile; any other interval is
            # device compute (productive)
            _goodput.note("compile" if (fresh_shape or perf_fresh)
                          else "productive", dt)
        if fresh_shape:
            self._obs["compile_count"].inc()
            self._obs["compile"].observe(dt)
        if sp is not None:
            if fresh_shape:
                sp.add_event("recompile", {"signature_count": len(
                    self._shape_signatures)})
            sp.end()
        if batch_n and dt > 0:
            self._obs["eps"].observe(batch_n * k / dt)
        self._obs["steps"].set(self._step_count)
        if self._guard is not None:
            self._buffer_guard_outs(verdicts, gnorms, losses,
                                    self._step_count - k, k)
            self._buffer_metric_outs(metric_outs, k, verdicts=verdicts)
        else:
            if flags.get_flag("check_nan_inf"):
                self._buffer_nan_check(losses, self._step_count - k, k)
            self._buffer_metric_outs(metric_outs, k)
        out = []
        for i in range(k):
            logs: Dict[str, Any] = {"loss": _SlabScalar(losses, i)}
            self._attach_metric_logs(logs)
            out.append(logs)
        return out

    # deferred-metric backstop: if nothing displays for this many
    # buffered entries (verbose=0 fit, long evaluate loops), drain
    # anyway — bounds live device buffers held by the pending list
    _PENDING_DRAIN_CAP = 64

    # -- deferred metric coercion -------------------------------------------
    def _buffer_metric_outs(self, metric_outs, nsteps: int,
                            verdicts=None) -> None:
        """``verdicts`` (guard-armed train paths only) rides along so
        the drain can DROP device-masked steps' metric rows: a skipped
        step's forward ran on the poisoned batch (NaN logits), and
        folding that row would pollute the accumulators of a step the
        model never trained on — metrics must match the clean run
        minus the batch, like the params do."""
        if self._metrics:
            if len(self._metric_pending) >= self._PENDING_DRAIN_CAP:
                self._drain_metric_updates()
            self._metric_pending.append((metric_outs, nsteps, verdicts))

    def _attach_metric_logs(self, logs: Dict[str, Any]) -> None:
        for m in self._metrics:
            names = m.name() if isinstance(m.name(), list) else [m.name()]
            for j, n in enumerate(names):
                logs[n] = _LazyMetricValue(self, m, j)

    def _drain_metric_updates(self) -> None:
        """Fold every buffered device metric output into the host-side
        accumulators — ONE sync for all steps since the last drain
        (log_freq/epoch boundaries), the deferral train_loop_drain_
        seconds measures. Buffered guard verdicts and deferred
        check_nan_inf losses drain here too (same single sync); their
        policy escalations (GuardRollback / GuardAbort /
        FloatingPointError) surface from this boundary."""
        if self._metric_pending:
            t0 = time.perf_counter()
            with _trace.phase(
                    "train.metric_drain",
                    attrs={"pending": len(self._metric_pending)}):
                pending, self._metric_pending = self._metric_pending, []
                for outs, nsteps, verdicts in pending:
                    keep = None
                    if verdicts is not None:
                        v = np.asarray(verdicts).reshape(-1)
                        masked = v == 1
                        if self._guard is not None \
                                and self._guard.mask_spikes:
                            masked = masked | (v == 2)
                        if masked.any():
                            keep = ~masked
                    for m, mo in zip(self._metrics, outs):
                        mo = _as_tuple(mo)
                        if keep is None:
                            m.update_stacked(mo, nsteps)
                        elif nsteps == 1:
                            if keep[0]:
                                m.update_stacked(mo, 1)
                        else:
                            # drop the device-masked rows; the rest
                            # keep per-step update semantics. Coerce
                            # each stacked array ONCE, not per row
                            mos = tuple(np.asarray(o) for o in mo)
                            for i in range(nsteps):
                                if keep[i]:
                                    m.update(*(o[i] for o in mos))
            if self._obs_loop is None:
                self._obs_loop = _loop_metrics()
            drain_dt = time.perf_counter() - t0
            self._obs_loop["drain"].observe(drain_dt)
            if _perf.enabled():
                # the deferred device→host sync: the "transfer/drain"
                # leg of the /perfz step-time breakdown
                _perf.record_phase("train", "drain", drain_dt)
            if _goodput.enabled():
                # a measured host-overhead window — recorded with the
                # weakest claim, so overlapping device work keeps
                # ownership of any shared seconds
                _goodput.note("host_gap", drain_dt)
        if self._guard_pending or self._nan_pending:
            self._drain_guard_checks()

    def drain_metrics(self) -> None:
        """Public flush for manual ``train_batch``/``eval_batch`` loops:
        fold all deferred device metric outputs into the Metric
        accumulators so ``metric.accumulate()`` reflects every step so
        far. ``fit``/``evaluate`` and log-value reads call this
        implicitly at display boundaries."""
        self._drain_metric_updates()

    def eval_batch(self, inputs, labels=None) -> Dict[str, Any]:
        """Single forward/metric step. Metric outputs are deferred like
        the train path — manual loops call ``drain_metrics()`` (or read
        a returned log value) before ``metric.accumulate()``;
        ``evaluate`` does so automatically."""
        self._sync_state_in()
        if self._eval_step_fn is None:
            self._eval_step_fn = self._build_eval_step()
        inputs = _as_tuple(inputs)
        labels = _as_tuple(labels) if labels is not None else ()
        self._guard_recompiles(inputs, labels)
        if self._shard_batch is not None:
            inputs = self._shard_batch(inputs)
            labels = self._shard_batch(labels)
        key = rng.split_for_step(self._step_count)
        loss, metric_outs = self._eval_step_fn(
            self._params, self._frozen, self._buffers, key, inputs, labels)
        logs = {}
        if loss is not None:
            logs["loss"] = loss  # device value; coerced by the consumer
        # buffered like the train path — evaluate()/accumulate drains
        self._buffer_metric_outs(metric_outs, 1)
        self._attach_metric_logs(logs)
        return logs

    def predict_batch(self, inputs):
        self._sync_state_in()
        if self._predict_fn is None:
            self._predict_fn = self._build_predict_step()
        inputs = _as_tuple(inputs)
        return self._predict_fn(self._params, self._frozen, self._buffers,
                                inputs)

    # -- preemption-safe training state (ISSUE 8) ---------------------------
    def _save_training_state(self, mgr, loader, epoch: int,
                             boundary: bool = False,
                             force: bool = False) -> None:
        """Checkpoint the COMPLETE training state: params/opt-state/
        buffers as the array tree, plus a small manifest ``state``
        bundle — global step, epoch, DataLoader cursor, the RNG base
        key, and pickled metric accumulators. With an async manager
        the call stalls only for the device→host snapshot; the commit
        overlaps the next train steps. Pending device metric buffers
        are drained FIRST, so the snapshot never loses in-flight
        metric state.

        ``boundary=True`` means the epoch (and its pass over the
        loader) is COMPLETE: the state records the NEXT epoch at batch
        0 — resuming from an exhausted cursor would replay the
        finished epoch's on_epoch_begin/eval/on_epoch_end over an
        empty train pass."""
        if self._params is None:
            self._sync_state_in()
        self._drain_metric_updates()
        tree = {"params": self._params, "opt": self._opt_state}
        if self._frozen:
            tree["frozen"] = self._frozen
        if self._buffers:
            tree["buffers"] = self._buffers
        if self._guard_state is not None:
            # the numeric guard's EMA carry: resume (and guard
            # rollback) keeps the spike baseline instead of re-warming
            tree["guard"] = self._guard_state
        key_data = np.asarray(
            jax.random.key_data(rng.get_global_stream()._key))
        cursor = loader.state_dict()
        if boundary:
            cursor = {"pass": int(cursor["pass"]) + 1, "batch": 0}
            epoch = epoch + 1
        state = {
            "step": int(self._step_count),
            "epoch": int(epoch),
            "loader": cursor,
            "rng": {"seed": int(rng._tls.global_seed),
                    "key_data": key_data.tolist(),
                    "key_dtype": str(key_data.dtype)},
            "metrics": base64.b64encode(pickle.dumps(
                [m.__dict__ for m in self._metrics],
                protocol=4)).decode("ascii"),
        }
        mgr.save(self._step_count, tree, state=state, force=force)

    def _restore_training_state(self, mgr, resume, loader):
        """Resume from ``mgr``: newest verified step for
        ``resume="auto"`` (or the step pinned by
        ``$PADDLE_ELASTIC_RESUME_STEP`` — an elastic respawn's hint —
        falling back to auto if that step is gone or corrupt), an
        explicit int otherwise. Returns the manifest state bundle, or
        None when the directory has no checkpoint (fresh start)."""
        from ..io.checkpoint import CheckpointCorrupt
        # identity/string checks, NOT `resume in (True, "auto")`:
        # 1 == True in Python, and resume=1 must mean STEP 1
        auto = resume == "auto" or resume is True
        step = None
        if not auto:
            step = int(resume)
        else:
            env = os.environ.get("PADDLE_ELASTIC_RESUME_STEP")
            if env:
                step = int(env)
        try:
            try:
                tree, state = mgr.restore_with_state(step)
            except (CheckpointCorrupt, FileNotFoundError):
                if not auto or step is None:
                    raise
                # the env-pinned step is gone or rotted: auto falls
                # back to the newest verifying step
                tree, state = mgr.restore_with_state(None)
        except FileNotFoundError:
            # only auto treats an empty directory as a fresh start; an
            # explicit resume=<step> that is missing (GC'd, mistyped)
            # must not silently retrain from step 0
            if not auto:
                raise
            return None
        # jnp.array(copy=True), NOT asarray: on CPU backends asarray
        # can zero-copy ALIAS the restored numpy buffers, and the
        # fused train loop then DONATES them — freeing the numpy tree
        # turns the live params into use-after-free garbage (same
        # hazard as the save-side snapshot, mirrored)
        put = lambda t: jax.tree_util.tree_map(  # noqa: E731
            lambda x: jnp.array(x, copy=True), t)
        self._params = put(tree["params"])
        self._frozen = put(tree.get("frozen") or {})
        self._buffers = put(tree.get("buffers") or {})
        self._opt_state = put(tree["opt"])
        if tree.get("guard") is not None:
            self._guard_state = put(tree["guard"])
        state = dict(state or {})
        self._step_count = int(state.get("step", mgr.latest_step() or 0))
        rng_state = state.get("rng")
        if rng_state:
            # the base key, not just the seed: next_key() calls before
            # fit() advance the stream past from_seed(seed)
            rng._tls.global_seed = int(rng_state["seed"])
            key = jax.random.wrap_key_data(jnp.asarray(np.asarray(
                rng_state["key_data"],
                dtype=rng_state.get("key_dtype", "uint32"))))
            rng._tls.stack = [rng.KeyStream(key)]
        blob = state.get("metrics")
        if blob:
            for m, st in zip(self._metrics,
                             pickle.loads(base64.b64decode(blob))):
                m.__dict__.update(st)
        cursor = state.get("loader")
        if cursor:
            loader.load_state_dict(cursor)
        # rebind network attributes so save()/state_dict() see the
        # restored values (same invalidation contract as Model.load)
        self._sync_state_out()
        return state

    def _guard_rollback(self, mgr, loader, epoch: int, rb) -> int:
        """Recover from a :class:`reliability.guard.GuardRollback`
        raised at a drain boundary inside ``fit``: restore the newest
        VERIFIED checkpoint (manifest path — params, opt state, RNG
        key, metric accumulators, guard EMA), then fast-forward the
        DataLoader cursor ``rb.stride`` batches PAST the offending
        step, so the poisoned range is never re-consumed. Returns the
        in-epoch batch index training resumes at. Steps between the
        checkpoint and the trip are discarded along with their
        batches — rollback trades that window for a clean restart
        (escalating stride clears a poisoned RANGE on repeat trips).
        Assumes the trip landed in the checkpoint's epoch; a
        cross-epoch trip fast-forwards within the checkpoint's pass.
        No checkpoint manager / no committed step escalates to
        :class:`GuardAbort`."""
        if mgr is None:
            raise self._guard.escalate(
                rb.step, rb.kind,
                "rollback requested but fit() has no checkpoint_dir",
                model=self) from rb
        # drop buffered device state from the poisoned window — the
        # restore rewinds metric accumulators to the manifest bundle
        self._metric_pending.clear()
        self._guard_pending.clear()
        self._nan_pending.clear()
        # EXPLICIT step, never resume="auto": auto honors the
        # $PADDLE_ELASTIC_RESUME_STEP pin an elastic respawn leaves in
        # the environment for the whole process — a mid-run rollback
        # must restore the newest verified step AT OR BELOW the trip
        # (every save drains first, so newer-than-trip can't commit;
        # the <= filter keeps that a local invariant), walking past
        # steps that rotted since their manifest verified
        from ..io.checkpoint import CheckpointCorrupt
        mgr.wait_until_finished()  # in-flight async commits manifest
        cand = [s for s in mgr.verified_steps() if s <= rb.step]
        st = None
        while cand:
            try:
                st = self._restore_training_state(
                    mgr, cand.pop(), loader)
                break
            except (CheckpointCorrupt, FileNotFoundError):
                continue
        if st is None:
            raise self._guard.escalate(
                rb.step, rb.kind,
                "rollback requested before any verified checkpoint "
                "committed", model=self) from rb
        ck_step = int(st.get("step", 0))
        cur = dict(st.get("loader") or {"pass": epoch, "batch": 0})
        tripped = int(cur["batch"]) + (rb.step - ck_step)
        target = tripped + rb.stride
        loader.load_state_dict({"pass": int(cur["pass"]),
                                "batch": target})
        if _trace.active():
            _trace.start_span("train.guard", attrs={
                "kind": rb.kind, "action": "rollback",
                "step": rb.step, "restored_step": ck_step,
                "fast_forward_to_batch": target}).end()
        print(f"[numeric-guard] rollback: {rb.kind} at step {rb.step} "
              f"-> restored verified step {ck_step}, fast-forwarded "
              f"cursor past batch {tripped} (stride {rb.stride})",
              file=sys.stderr)
        return target

    # -- fit/evaluate/predict loops -----------------------------------------
    def _as_loader(self, data, batch_size, shuffle) -> DataLoader:
        if isinstance(data, DataLoader):
            return data
        if isinstance(data, Dataset):
            return DataLoader(data, batch_size=batch_size, shuffle=shuffle)
        raise TypeError(f"unsupported data type {type(data)}")

    def fit(self, train_data=None, eval_data=None, batch_size: int = 1,
            epochs: int = 1, eval_freq: int = 1, log_freq: int = 10,
            save_dir: Optional[str] = None, save_freq: int = 1,
            verbose: int = 2, drop_last: bool = False, shuffle: bool = True,
            num_workers: int = 0, callbacks=None,
            steps_per_loop: Optional[int] = None,
            checkpoint_dir: Optional[str] = None,
            checkpoint_freq: Optional[int] = None,
            resume=None, keep_checkpoints: int = 5,
            async_checkpoint: bool = True,
            preemption_guard=None,
            preemption_flush_budget: float = 30.0) -> None:
        """ref: hapi/model.py:1574.

        ``steps_per_loop`` (default ``FLAGS.steps_per_loop``) fuses K
        optimizer steps into one scanned XLA dispatch fed by
        double-buffered [K, ...] superbatches — losses are bit-identical
        to K=1 (see ``_build_train_loop`` for the exactness scope) while
        the per-step Python/dispatch overhead is paid once per slab. Callbacks still see per-step on_train_batch_begin/end
        (driven from the slab's stacked, lazily-coerced logs).

        Preemption-safe training (docs/RELIABILITY.md):

        - ``checkpoint_dir`` arms full-state checkpointing through
          ``io.checkpoint.CheckpointManager`` — every ``checkpoint_freq``
          optimizer steps (or each epoch when None), async by default:
          the loop stalls only for the device→host snapshot.
        - ``resume="auto"`` (or an explicit step) restores the newest
          VERIFIED checkpoint — params, optimizer state, RNG base key,
          DataLoader cursor (mid-epoch, mid-superbatch), and metric
          accumulators — and continues with a loss stream bit-identical
          to the uninterrupted run at any ``steps_per_loop``. An
          elastic respawn pins the step via
          ``$PADDLE_ELASTIC_RESUME_STEP``; no script change needed.
        - ``preemption_guard`` (an ``elastic.PreemptionGuard``) is
          polled at step boundaries: on SIGTERM the loop snapshots the
          current state, flushes it under ``preemption_flush_budget``
          seconds, and exits ``RESTART_EXIT_CODE``."""
        assert self._optimizer is not None and self._loss is not None, \
            "call prepare(optimizer, loss, ...) before fit()"
        loader = self._as_loader(train_data, batch_size, shuffle)
        eval_loader = self._as_loader(eval_data, batch_size, False) \
            if eval_data is not None else None
        if steps_per_loop is None:
            steps_per_loop = flags.get_flag("steps_per_loop")
        k_loop = max(int(steps_per_loop), 1)
        if k_loop > 1 and self._shard_batch is not None \
                and self._shard_superbatch is None:
            k_loop = 1  # no superbatch sharding hook wired: stay exact
        train_ckpt = None
        start_epoch = 0
        resume_step_in_epoch = 0
        if checkpoint_dir is not None:
            from ..io.checkpoint import CheckpointManager
            train_ckpt = CheckpointManager(
                checkpoint_dir, max_to_keep=keep_checkpoints,
                async_save=async_checkpoint)
            # not a truthiness gate: resume=0 means "restore STEP 0",
            # only None/False mean "don't resume"
            if resume is not None and resume is not False:
                st = self._restore_training_state(train_ckpt, resume,
                                                  loader)
                if st is not None:
                    start_epoch = int(st.get("epoch", 0))
                    resume_step_in_epoch = int(
                        (st.get("loader") or {}).get("batch", 0))
        last_ckpt_step = self._step_count
        last_ckpt_boundary = True  # restored/fresh state never replays

        def ckpt_tick(epoch: int, force: bool = False,
                      boundary: bool = False) -> None:
            """Step-boundary checkpoint cadence + preemption poll."""
            nonlocal last_ckpt_step, last_ckpt_boundary
            if train_ckpt is not None:
                stale = self._step_count != last_ckpt_step
                # an epoch-end tick UPGRADES a same-step mid-loop save:
                # that save recorded (epoch, exhausted cursor), which
                # would replay the finished epoch's callbacks/eval over
                # an empty train pass on resume
                upgrade = boundary and not stale and not last_ckpt_boundary
                if (stale and (force or (checkpoint_freq and
                                         self._step_count - last_ckpt_step
                                         >= checkpoint_freq))) or upgrade:
                    self._save_training_state(train_ckpt, loader, epoch,
                                              boundary=boundary,
                                              force=upgrade)
                    last_ckpt_step = self._step_count
                    last_ckpt_boundary = boundary
            if preemption_guard is not None and preemption_guard.triggered:
                def _flush():
                    if train_ckpt is None:
                        return
                    from ..reliability.retry import Deadline
                    dl = Deadline.after(preemption_flush_budget)
                    outcome = None
                    if self._step_count != last_ckpt_step:
                        # drain queued commits FIRST: save()'s bounded
                        # queue blocks (no deadline) while a snapshot
                        # is queued behind a slow commit — snapshotting
                        # into a backed-up writer could eat the whole
                        # grace budget before flush() ever ran
                        drained = train_ckpt.flush(dl)
                        if drained in ("committed", "noop"):
                            # fresh snapshot of the CURRENT step
                            # (stalls only for the device→host copy)
                            self._save_training_state(
                                train_ckpt, loader, epoch,
                                boundary=boundary)
                        else:
                            outcome = drained  # timeout/error: the
                            # previous manifested step stands
                    if outcome is None:
                        outcome = train_ckpt.flush(dl)
                    print(f"[preemption] emergency checkpoint flush: "
                          f"{outcome} (step {self._step_count})",
                          file=sys.stderr)
                # runs _flush then exits RESTART_EXIT_CODE
                preemption_guard.check(save=_flush)

        try:
            steps = len(loader)
        except TypeError:
            steps = None
        cbks = config_callbacks(callbacks, model=self, epochs=epochs,
                                steps=steps, verbose=verbose,
                                log_freq=log_freq,
                                metrics=[m.name() for m in self._metrics],
                                save_dir=save_dir)
        self.stop_training = False
        cbks.on_train_begin()
        logs: Dict[str, Any] = {}
        epoch_done = start_epoch - 1  # last fully completed epoch
        try:
            for epoch in range(start_epoch, epochs):
                if self.stop_training:
                    break
                cbks.on_epoch_begin(epoch)
                # epoch span: entered on the fit thread's stack so the
                # dispatch/step/drain spans below parent under it. The
                # finally closes it even when an exception unwinds (a
                # caller catching a step failure and re-running fit must
                # not inherit a stale epoch at the bottom of the
                # thread-local stack); Span.__exit__ records the error.
                ep_span = _trace.span(
                    "train.epoch", attrs={"epoch": epoch}).__enter__() \
                    if _trace.active() else None
                step = resume_step_in_epoch if epoch == start_epoch else 0
                try:
                    # fold any still-buffered outputs BEFORE reset — the
                    # Metric objects then hold exactly what the
                    # immediate-update path held at every reset boundary.
                    # A mid-epoch RESUME (step > 0) skips the reset: the
                    # restored accumulators ARE this epoch's state so far.
                    if step == 0:
                        self._drain_metric_updates()
                        for m in self._metrics:
                            m.reset()
                    while True:
                        # one epoch pass; restarts after a numeric-guard
                        # ROLLBACK (the newest verified checkpoint is
                        # restored and the loader cursor fast-forwarded
                        # past the offending range, so the fresh
                        # iterator resumes there)
                        if k_loop > 1:
                            it = loader.superbatches(k_loop)
                        else:
                            it = iter(loader)
                        try:
                            while True:
                                with _trace.phase("fit.next_batch"):
                                    batch = next(it, None)
                                if batch is None:
                                    break
                                inputs, labels = self._split_batch(batch)
                                if k_loop > 1:
                                    k = int(np.shape(
                                        jax.tree_util.tree_leaves(
                                            inputs)[0])[0])
                                    if k == k_loop:
                                        with _trace.phase("fit.dispatch"):
                                            step_logs = \
                                                self.train_loop_batch(
                                                    inputs, labels)
                                        with _trace.phase("fit.callbacks"):
                                            for logs in step_logs:
                                                cbks.on_train_batch_begin(
                                                    step)
                                                cbks.on_train_batch_end(
                                                    step, logs)
                                                step += 1
                                        ckpt_tick(epoch)
                                        continue
                                    # ragged tail slab (< K stacked
                                    # steps): unstack and run the
                                    # per-step path — same math, one
                                    # extra signature at most (the K=1
                                    # program)
                                    sub_batches = [
                                        jax.tree_util.tree_map(
                                            lambda x: x[i],
                                            (inputs, labels))
                                        for i in range(k)]
                                else:
                                    sub_batches = [(inputs, labels)]
                                for inp, lab in sub_batches:
                                    cbks.on_train_batch_begin(step)
                                    with _trace.phase("fit.dispatch"):
                                        logs = self.train_batch(inp, lab)
                                    with _trace.phase("fit.callbacks"):
                                        cbks.on_train_batch_end(step,
                                                                logs)
                                    step += 1
                                ckpt_tick(epoch)
                            # tail drain INSIDE the rollback scope: a
                            # trip buffered by the pass's last batches
                            # must escalate here, where a rollback can
                            # still restart this epoch's iteration
                            self._drain_metric_updates()
                            break
                        except _nguard.GuardRollback as rb:
                            step = self._guard_rollback(train_ckpt,
                                                        loader, epoch,
                                                        rb)
                            last_ckpt_step = self._step_count
                            if hasattr(it, "close"):
                                it.close()
                    # freeze the epoch's final train logs NOW (epoch
                    # boundary = display boundary): the eval pass below
                    # resets the shared metric accumulators, which would
                    # otherwise leak into the lazily-coerced train values
                    # at on_epoch_end
                    logs = {n: float(v) if isinstance(
                        v, (_LazyMetricValue, _SlabScalar)) else v
                        for n, v in logs.items()}
                    if eval_loader is not None and epoch % eval_freq == 0:
                        with _trace.span("fit.eval"):
                            eval_logs = self.evaluate(
                                eval_loader, verbose=0, _callbacks=cbks)
                        logs.update({f"eval_{k}": v
                                     for k, v in eval_logs.items()})
                    cbks.on_epoch_end(epoch, logs)
                    # epoch-granular checkpoint default (checkpoint_freq
                    # None): one full-state save per completed epoch
                    ckpt_tick(epoch, force=checkpoint_freq is None,
                              boundary=True)
                    epoch_done = epoch
                finally:
                    if ep_span is not None:
                        ep_span.set_attr("steps", step)
                        ep_span.__exit__(*sys.exc_info())
            cbks.on_train_end(logs)
            self._sync_state_out()
            if train_ckpt is not None:
                # final full-state save (no-op if the last step is already
                # boundary-checkpointed): the fit-exit barrier in the
                # finally below makes every queued async commit durable
                # before fit returns. Keyed to the last COMPLETED epoch —
                # a stop_training break leaves `epoch` naming an epoch
                # that never ran, and a boundary save against it would
                # resume PAST it
                ckpt_tick(epoch_done, force=True, boundary=True)
        finally:
            if train_ckpt is not None:
                # fit-exit barrier, exception path included: wait out
                # in-flight async commits and stop the writer thread.
                # A close/commit failure must not mask an exception
                # already unwinding through fit — but on a clean exit
                # it IS fit's failure (the final save never committed)
                unwinding = sys.exc_info()[0] is not None
                try:
                    train_ckpt.close()
                except BaseException:
                    if not unwinding:
                        raise


    def evaluate(self, eval_data, batch_size: int = 1, log_freq: int = 10,
                 verbose: int = 2, num_workers: int = 0, callbacks=None,
                 _callbacks=None) -> Dict[str, Any]:
        """ref: hapi/model.py:1709."""
        loader = self._as_loader(eval_data, batch_size, False)
        cbks = _callbacks or config_callbacks(
            callbacks, model=self, verbose=verbose,
            metrics=[m.name() for m in self._metrics])
        cbks.on_eval_begin()
        # drain-then-reset: buffered train-step outputs fold in first,
        # so Metric state at this boundary matches the pre-deferral
        # immediate-update semantics
        self._drain_metric_updates()
        for m in self._metrics:
            m.reset()
        losses = []
        for step, batch in enumerate(loader):
            cbks.on_eval_batch_begin(step)
            inputs, labels = self._split_batch(batch)
            logs = self.eval_batch(inputs, labels)
            if "loss" in logs:
                losses.append(logs["loss"])
            cbks.on_eval_batch_end(step, logs)
        out: Dict[str, Any] = {}
        if losses:
            out["loss"] = float(np.mean(losses))
        self._drain_metric_updates()
        for m in self._metrics:
            res = m.accumulate()
            names = m.name() if isinstance(m.name(), list) else [m.name()]
            vals = res if isinstance(res, list) else [res]
            for n, v in zip(names, _as_tuple(vals)):
                out[n] = float(v)
        cbks.on_eval_end(out)
        return out

    def predict(self, test_data, batch_size: int = 1,
                num_workers: int = 0, stack_outputs: bool = False):
        """ref: hapi/model.py:1791."""
        loader = self._as_loader(test_data, batch_size, False)
        outputs = []
        for batch in loader:
            inputs = _as_tuple(batch)
            # predict data has no labels
            out = self.predict_batch(inputs)
            outputs.append(jax.tree_util.tree_map(np.asarray, out))
        if stack_outputs and outputs:
            outputs = jax.tree_util.tree_map(
                lambda *xs: np.concatenate(xs, axis=0), *outputs)
        return outputs

    # -- persistence --------------------------------------------------------
    def save(self, path: str, training: bool = True) -> None:
        """Saves ``path + '.pdparams'`` (+ ``.pdopt`` when training=True)
        (ref: hapi/model.py save → fluid save_dygraph)."""
        self._sync_state_out()
        d = os.path.dirname(os.path.abspath(path))
        os.makedirs(d, exist_ok=True)
        state = {k: np.asarray(v)
                 for k, v in self.network.state_dict().items()}
        with open(path + ".pdparams", "wb") as f:
            pickle.dump(state, f, protocol=4)
        if training and self._optimizer is not None:
            opt_state = jax.tree_util.tree_map(
                np.asarray, {"state": self._opt_state,
                             "step": self._step_count})
            with open(path + ".pdopt", "wb") as f:
                pickle.dump(opt_state, f, protocol=4)

    def load(self, path: str, reset_optimizer: bool = False) -> None:
        with open(path + ".pdparams", "rb") as f:
            state = pickle.load(f)
        self.network.set_state_dict(state)
        self._params = None
        self._frozen = None
        self._buffers = None
        if not reset_optimizer and os.path.exists(path + ".pdopt"):
            with open(path + ".pdopt", "rb") as f:
                opt = pickle.load(f)
            self._opt_state = jax.tree_util.tree_map(
                jnp.asarray, opt["state"])
            self._step_count = int(opt["step"])
        else:
            self._opt_state = None

    def parameters(self):
        return self.network.parameters()

    def summary(self, input_size=None, dtype=None) -> Dict[str, int]:
        """Per-layer table + parameter counts (ref: hapi/model.py
        summary → model_summary.py; shapes come from a zero-cost
        eval_shape probe)."""
        from .summary import summary as _summary
        multi = isinstance(input_size, (list, tuple)) and input_size \
            and isinstance(input_size[0], (list, tuple))
        n = len(input_size) if multi else 1
        return _summary(self.network, input_size,
                        [dtype] * n if dtype else None)
