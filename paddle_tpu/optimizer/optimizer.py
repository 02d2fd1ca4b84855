"""Optimizers.

Rebuild of the reference's optimizer library
(reference: python/paddle/optimizer/{optimizer,sgd,momentum,adam,adamw,
adagrad,adadelta,adamax,rmsprop,lamb}.py, kernels in
paddle/phi/kernels/gpu/{adam,sgd,...}_kernel.cu; LARS in
paddle/fluid/operators/optimizers/lars_momentum_op.cu).

Architecture: every optimizer is a pure functional core —
``init(params) -> state`` and ``update(grads, state, params, lr) ->
(new_params, new_state)`` — wrapped in a stateful Paddle-style object.
The functional core is what compiled train steps (hapi/Model, parallel
trainers) jit; the stateful ``step()`` serves eager workflows by writing
updated arrays back into the bound Layer. Master-weight support
(``multi_precision`` in the reference kernels) falls out naturally: state
keeps fp32 copies when params are bf16.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp

from ..nn.clip import GradClipBase
from ..nn.layer import Layer
from .lr import LRScheduler, make_schedule

PyTree = Any


def _tree_map(fn, *trees, is_leaf=None):
    return jax.tree_util.tree_map(fn, *trees, is_leaf=is_leaf)


def _cast_like(new, ref):
    return _tree_map(lambda n, r: n.astype(r.dtype), new, ref)


class Optimizer:
    """Base class. Subclasses implement ``init_state`` and ``_update``."""

    def __init__(self, learning_rate=0.001, parameters=None,
                 weight_decay: float = 0.0, grad_clip: GradClipBase = None,
                 multi_precision: bool = True):
        self._lr = learning_rate
        self.lr_fn = make_schedule(learning_rate)
        self.weight_decay = float(weight_decay or 0.0)
        self.grad_clip = grad_clip
        self.multi_precision = multi_precision
        self._layer: Optional[Layer] = None
        self._params: Optional[Dict[str, jax.Array]] = None
        self._state: Optional[PyTree] = None
        self._step_count = 0
        if isinstance(parameters, Layer):
            self._layer = parameters
        elif parameters is not None:
            self._params = dict(parameters) if isinstance(parameters, dict) \
                else None
            if self._params is None:
                # list of arrays: keep positional names
                self._params = {str(i): p for i, p in enumerate(parameters)}

    # -- functional core ----------------------------------------------------
    def init_state(self, params: PyTree) -> PyTree:
        raise NotImplementedError

    def _update(self, grads, state, params, lr):
        """Return (updates, new_state) where updates are *deltas* added to
        params (already including lr and weight decay)."""
        raise NotImplementedError

    def _master(self, params):
        if not self.multi_precision:
            return params
        return _tree_map(
            lambda p: p.astype(jnp.float32)
            if p.dtype in (jnp.bfloat16, jnp.float16) else p, params)

    def apply_gradients(self, params: PyTree, grads: PyTree, state: PyTree,
                        step) -> tuple[PyTree, PyTree]:
        """Pure update — jit this. ``state`` must come from ``init_state``.
        ``step`` drives the LR schedule on-device."""
        with jax.named_scope("optimizer"):
            if self.grad_clip is not None:
                grads = self.grad_clip(grads)
            lr = self.lr_fn(jnp.asarray(step))
            master = state.get("master") if isinstance(state, dict) \
                else None
            work_params = master if master is not None else params
            updates, new_state = self._update(grads, state, work_params,
                                              lr)
            new_work = _tree_map(jnp.add, work_params, updates)
            if master is not None:
                new_state["master"] = new_work
            return _cast_like(new_work, params), new_state

    def _maybe_master_state(self, params) -> dict:
        state: Dict[str, Any] = {}
        if self.multi_precision and any(
                p.dtype in (jnp.bfloat16, jnp.float16)
                for p in jax.tree_util.tree_leaves(params)):
            state["master"] = self._master(params)
        return state

    # -- stateful / eager API (Paddle style) --------------------------------
    def _bound_params(self) -> Dict[str, jax.Array]:
        if self._layer is not None:
            return dict(self._layer.named_parameters())
        if self._params is not None:
            return self._params
        raise ValueError("optimizer has no bound parameters")

    def step(self, grads: Dict[str, jax.Array]) -> None:
        """Eager update: applies grads and writes params back into the
        bound Layer (analog of ``optimizer.step()`` after
        ``loss.backward()`` — here grads come from jax.grad). Only
        parameters present in ``grads`` are updated, so frozen
        (trainable=False) params — absent from autograd.record's grad
        dict — pass through untouched instead of breaking the tree
        match."""
        params = self._bound_params()
        missing = [k for k in grads if k not in params]
        if missing:
            raise KeyError(
                f"grads for unknown parameters {missing[:3]}... — for "
                "autograd.record over multiple layers, use one "
                "optimizer per layer with tape.layer_grads(i)")
        upd = {k: params[k] for k in grads}
        if self._state is None:
            self._state = self.init_state(upd)
        new_upd, self._state = self.apply_gradients(
            upd, grads, self._state, self._step_count)
        self._step_count += 1
        new_params = {**params, **new_upd}
        if self._layer is not None:
            for name, v in new_upd.items():
                self._layer._assign_by_path(name, v)
        else:
            self._params = new_params

    def minimize(self, loss_fn: Callable, *args):
        params = self._bound_params()
        grads = jax.grad(loss_fn)(params, *args)
        self.step(grads)

    def clear_grad(self) -> None:  # grads are functional; nothing to clear
        pass

    clear_gradients = clear_grad

    def get_lr(self) -> float:
        if isinstance(self._lr, LRScheduler):
            return self._lr.get_lr()
        return float(self._lr)

    def set_lr(self, value: float) -> None:
        self._lr = float(value)
        self.lr_fn = make_schedule(value)

    def state_dict(self) -> dict:
        return {"state": self._state, "step": self._step_count}

    def set_state_dict(self, sd: dict) -> None:
        self._state = sd["state"]
        self._step_count = sd["step"]

    @property
    def _learning_rate(self):
        return self._lr


class SGD(Optimizer):
    """ref: python/paddle/optimizer/sgd.py; phi sgd kernel."""

    def init_state(self, params):
        return self._maybe_master_state(params)

    def _update(self, grads, state, params, lr):
        def upd(g, p):
            g = g.astype(p.dtype)
            if self.weight_decay:
                g = g + self.weight_decay * p
            return -lr * g
        return _tree_map(upd, grads, params), state


class Momentum(Optimizer):
    """ref: python/paddle/optimizer/momentum.py (use_nesterov supported)."""

    def __init__(self, learning_rate=0.001, momentum: float = 0.9,
                 parameters=None, use_nesterov: bool = False,
                 weight_decay: float = 0.0, grad_clip=None,
                 multi_precision: bool = True):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         multi_precision)
        self.momentum = momentum
        self.use_nesterov = use_nesterov

    def init_state(self, params):
        s = self._maybe_master_state(params)
        base = s.get("master", params)
        s["velocity"] = _tree_map(jnp.zeros_like, base)
        return s

    def _update(self, grads, state, params, lr):
        mu = self.momentum

        def upd(g, v, p):
            g = g.astype(p.dtype)
            if self.weight_decay:
                g = g + self.weight_decay * p
            v_new = mu * v + g
            if self.use_nesterov:
                delta = -lr * (g + mu * v_new)
            else:
                delta = -lr * v_new
            return delta, v_new
        pairs = _tree_map(upd, grads, state["velocity"], params)
        updates = _tree_map(lambda pr: pr[0], pairs,
                            is_leaf=lambda x: isinstance(x, tuple))
        new_v = _tree_map(lambda pr: pr[1], pairs,
                          is_leaf=lambda x: isinstance(x, tuple))
        new_state = dict(state)
        new_state["velocity"] = new_v
        return updates, new_state


class Adam(Optimizer):
    """ref: python/paddle/optimizer/adam.py; phi adam kernel
    (bias-corrected, epsilon outside sqrt as in the reference)."""

    _decoupled_wd = False  # Adam couples wd into grad; AdamW decouples

    def __init__(self, learning_rate=0.001, beta1: float = 0.9,
                 beta2: float = 0.999, epsilon: float = 1e-8,
                 parameters=None, weight_decay: float = 0.0,
                 grad_clip=None, multi_precision: bool = True,
                 lazy_mode: bool = False):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         multi_precision)
        self.beta1, self.beta2, self.epsilon = beta1, beta2, epsilon

    def init_state(self, params):
        s = self._maybe_master_state(params)
        base = s.get("master", params)
        s["m"] = _tree_map(jnp.zeros_like, base)
        s["v"] = _tree_map(jnp.zeros_like, base)
        s["t"] = jnp.zeros([], jnp.int32)
        return s

    def _decay_mask(self, params):
        """Per-param decay on/off honoring apply_decay_param_fun
        (ref: python/paddle/optimizer/adamw.py apply_decay_param_fun)."""
        fn = getattr(self, "apply_decay_param_fun", None)
        if fn is None:
            return _tree_map(lambda p: True, params)
        return {name: bool(fn(name)) for name in params} \
            if isinstance(params, dict) else \
            _tree_map(lambda p: True, params)

    def _update(self, grads, state, params, lr):
        b1, b2, eps = self.beta1, self.beta2, self.epsilon
        t = state["t"] + 1
        bc1 = 1 - b1 ** t.astype(jnp.float32)
        bc2 = 1 - b2 ** t.astype(jnp.float32)
        decay_mask = self._decay_mask(params)

        def upd(g, m, v, p, do_decay):
            g = g.astype(p.dtype)
            if self.weight_decay and not self._decoupled_wd and do_decay:
                g = g + self.weight_decay * p
            m_new = b1 * m + (1 - b1) * g
            v_new = b2 * v + (1 - b2) * jnp.square(g)
            m_hat = m_new / bc1
            v_hat = v_new / bc2
            delta = -lr * m_hat / (jnp.sqrt(v_hat) + eps)
            if self.weight_decay and self._decoupled_wd and do_decay:
                delta = delta - lr * self.weight_decay * p
            return delta, m_new, v_new
        triples = _tree_map(upd, grads, state["m"], state["v"], params,
                            decay_mask)
        is_t = lambda x: isinstance(x, tuple)  # noqa: E731
        updates = _tree_map(lambda tr: tr[0], triples, is_leaf=is_t)
        new_m = _tree_map(lambda tr: tr[1], triples, is_leaf=is_t)
        new_v = _tree_map(lambda tr: tr[2], triples, is_leaf=is_t)
        new_state = dict(state)
        new_state.update(m=new_m, v=new_v, t=t)
        return updates, new_state


class AdamW(Adam):
    """ref: python/paddle/optimizer/adamw.py — decoupled weight decay."""

    _decoupled_wd = True

    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-8, parameters=None, weight_decay: float = 0.01,
                 grad_clip=None, multi_precision: bool = True,
                 apply_decay_param_fun: Optional[Callable] = None):
        super().__init__(learning_rate, beta1, beta2, epsilon, parameters,
                         weight_decay, grad_clip, multi_precision)
        self.apply_decay_param_fun = apply_decay_param_fun


class Adagrad(Optimizer):
    def __init__(self, learning_rate=0.001, epsilon: float = 1e-6,
                 parameters=None, weight_decay: float = 0.0,
                 grad_clip=None, initial_accumulator_value: float = 0.0,
                 multi_precision: bool = True):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         multi_precision)
        self.epsilon = epsilon
        self.init_acc = initial_accumulator_value

    def init_state(self, params):
        s = self._maybe_master_state(params)
        base = s.get("master", params)
        s["acc"] = _tree_map(
            lambda p: jnp.full_like(p, self.init_acc), base)
        return s

    def _update(self, grads, state, params, lr):
        def upd(g, a, p):
            g = g.astype(p.dtype)
            if self.weight_decay:
                g = g + self.weight_decay * p
            a_new = a + jnp.square(g)
            return -lr * g / (jnp.sqrt(a_new) + self.epsilon), a_new
        pairs = _tree_map(upd, grads, state["acc"], params)
        is_t = lambda x: isinstance(x, tuple)  # noqa: E731
        updates = _tree_map(lambda pr: pr[0], pairs, is_leaf=is_t)
        new_acc = _tree_map(lambda pr: pr[1], pairs, is_leaf=is_t)
        ns = dict(state)
        ns["acc"] = new_acc
        return updates, ns


class RMSProp(Optimizer):
    def __init__(self, learning_rate=0.001, rho: float = 0.95,
                 epsilon: float = 1e-6, momentum: float = 0.0,
                 centered: bool = False, parameters=None,
                 weight_decay: float = 0.0, grad_clip=None,
                 multi_precision: bool = True):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         multi_precision)
        self.rho, self.epsilon = rho, epsilon
        self.momentum, self.centered = momentum, centered

    def init_state(self, params):
        s = self._maybe_master_state(params)
        base = s.get("master", params)
        s["ms"] = _tree_map(jnp.zeros_like, base)
        s["mom"] = _tree_map(jnp.zeros_like, base)
        if self.centered:
            s["mg"] = _tree_map(jnp.zeros_like, base)
        return s

    def _update(self, grads, state, params, lr):
        rho, eps, mu = self.rho, self.epsilon, self.momentum

        def upd(g, ms, mom, p, mg=None):
            g = g.astype(p.dtype)
            if self.weight_decay:
                g = g + self.weight_decay * p
            ms_new = rho * ms + (1 - rho) * jnp.square(g)
            if mg is not None:
                mg_new = rho * mg + (1 - rho) * g
                denom = jnp.sqrt(ms_new - jnp.square(mg_new) + eps)
            else:
                mg_new = None
                denom = jnp.sqrt(ms_new + eps)
            mom_new = mu * mom + lr * g / denom
            return -mom_new, ms_new, mom_new, mg_new
        if self.centered:
            quads = _tree_map(upd, grads, state["ms"], state["mom"], params,
                              state["mg"])
        else:
            quads = _tree_map(upd, grads, state["ms"], state["mom"], params)
        is_t = lambda x: isinstance(x, tuple)  # noqa: E731
        ns = dict(state)
        ns["ms"] = _tree_map(lambda q: q[1], quads, is_leaf=is_t)
        ns["mom"] = _tree_map(lambda q: q[2], quads, is_leaf=is_t)
        if self.centered:
            ns["mg"] = _tree_map(lambda q: q[3], quads, is_leaf=is_t)
        return _tree_map(lambda q: q[0], quads, is_leaf=is_t), ns


class Adadelta(Optimizer):
    def __init__(self, learning_rate=1.0, rho: float = 0.95,
                 epsilon: float = 1e-6, parameters=None,
                 weight_decay: float = 0.0, grad_clip=None,
                 multi_precision: bool = True):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         multi_precision)
        self.rho, self.epsilon = rho, epsilon

    def init_state(self, params):
        s = self._maybe_master_state(params)
        base = s.get("master", params)
        s["avg_sq"] = _tree_map(jnp.zeros_like, base)
        s["avg_dx"] = _tree_map(jnp.zeros_like, base)
        return s

    def _update(self, grads, state, params, lr):
        rho, eps = self.rho, self.epsilon

        def upd(g, asq, adx, p):
            g = g.astype(p.dtype)
            if self.weight_decay:
                g = g + self.weight_decay * p
            asq_new = rho * asq + (1 - rho) * jnp.square(g)
            dx = -jnp.sqrt(adx + eps) / jnp.sqrt(asq_new + eps) * g
            adx_new = rho * adx + (1 - rho) * jnp.square(dx)
            return lr * dx, asq_new, adx_new
        trip = _tree_map(upd, grads, state["avg_sq"], state["avg_dx"],
                         params)
        is_t = lambda x: isinstance(x, tuple)  # noqa: E731
        ns = dict(state)
        ns["avg_sq"] = _tree_map(lambda t_: t_[1], trip, is_leaf=is_t)
        ns["avg_dx"] = _tree_map(lambda t_: t_[2], trip, is_leaf=is_t)
        return _tree_map(lambda t_: t_[0], trip, is_leaf=is_t), ns


class Adamax(Optimizer):
    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-8, parameters=None, weight_decay=0.0,
                 grad_clip=None, multi_precision: bool = True):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         multi_precision)
        self.beta1, self.beta2, self.epsilon = beta1, beta2, epsilon

    def init_state(self, params):
        s = self._maybe_master_state(params)
        base = s.get("master", params)
        s["m"] = _tree_map(jnp.zeros_like, base)
        s["u"] = _tree_map(jnp.zeros_like, base)
        s["t"] = jnp.zeros([], jnp.int32)
        return s

    def _update(self, grads, state, params, lr):
        b1, b2, eps = self.beta1, self.beta2, self.epsilon
        t = state["t"] + 1
        bc1 = 1 - b1 ** t.astype(jnp.float32)

        def upd(g, m, u, p):
            g = g.astype(p.dtype)
            if self.weight_decay:
                g = g + self.weight_decay * p
            m_new = b1 * m + (1 - b1) * g
            u_new = jnp.maximum(b2 * u, jnp.abs(g))
            return -lr / bc1 * m_new / (u_new + eps), m_new, u_new
        trip = _tree_map(upd, grads, state["m"], state["u"], params)
        is_t = lambda x: isinstance(x, tuple)  # noqa: E731
        ns = dict(state)
        ns["m"] = _tree_map(lambda t_: t_[1], trip, is_leaf=is_t)
        ns["u"] = _tree_map(lambda t_: t_[2], trip, is_leaf=is_t)
        ns["t"] = t
        return _tree_map(lambda t_: t_[0], trip, is_leaf=is_t), ns


class Lamb(Optimizer):
    """ref: python/paddle/optimizer/lamb.py; phi lamb kernel — layer-wise
    trust ratio on top of Adam (large-batch training, §2.3)."""

    def __init__(self, learning_rate=0.001, lamb_weight_decay: float = 0.01,
                 beta1=0.9, beta2=0.999, epsilon=1e-6, parameters=None,
                 grad_clip=None, exclude_from_weight_decay_fn=None,
                 multi_precision: bool = True):
        super().__init__(learning_rate, parameters, lamb_weight_decay,
                         grad_clip, multi_precision)
        self.beta1, self.beta2, self.epsilon = beta1, beta2, epsilon
        self.exclude_fn = exclude_from_weight_decay_fn

    def init_state(self, params):
        s = self._maybe_master_state(params)
        base = s.get("master", params)
        s["m"] = _tree_map(jnp.zeros_like, base)
        s["v"] = _tree_map(jnp.zeros_like, base)
        s["t"] = jnp.zeros([], jnp.int32)
        return s

    def _update(self, grads, state, params, lr):
        b1, b2, eps = self.beta1, self.beta2, self.epsilon
        t = state["t"] + 1
        bc1 = 1 - b1 ** t.astype(jnp.float32)
        bc2 = 1 - b2 ** t.astype(jnp.float32)
        if self.exclude_fn is not None and isinstance(params, dict):
            decay_mask = {n: not self.exclude_fn(n) for n in params}
        else:
            decay_mask = _tree_map(lambda p: True, params)

        def upd(g, m, v, p, do_decay):
            g = g.astype(p.dtype)
            m_new = b1 * m + (1 - b1) * g
            v_new = b2 * v + (1 - b2) * jnp.square(g)
            r = (m_new / bc1) / (jnp.sqrt(v_new / bc2) + eps)
            if self.weight_decay and do_decay:
                r = r + self.weight_decay * p
            w_norm = jnp.sqrt(jnp.sum(jnp.square(p)))
            r_norm = jnp.sqrt(jnp.sum(jnp.square(r)))
            trust = jnp.where((w_norm > 0) & (r_norm > 0),
                              w_norm / r_norm, 1.0)
            return -lr * trust * r, m_new, v_new
        trip = _tree_map(upd, grads, state["m"], state["v"], params,
                         decay_mask)
        is_t = lambda x: isinstance(x, tuple)  # noqa: E731
        ns = dict(state)
        ns["m"] = _tree_map(lambda t_: t_[1], trip, is_leaf=is_t)
        ns["v"] = _tree_map(lambda t_: t_[2], trip, is_leaf=is_t)
        ns["t"] = t
        return _tree_map(lambda t_: t_[0], trip, is_leaf=is_t), ns


class Adafactor(Optimizer):
    """Adafactor (Shazeer & Stern 2018) — sublinear-memory Adam.

    The reference has no analog (its big-model recipe is sharded Adam
    across a pod, python/paddle/distributed/fleet sharding stage 2/3);
    on a single TPU chip the memory answer is FACTORED second moments:
    for a [R, C] weight, store row/col statistics (R + C floats) instead
    of Adam's 2·R·C. GPT-2-XL (1.56B params) under AdamW needs ~12.5 GB
    of m/v state — over a v5e chip's HBM on top of fp32 params; under
    Adafactor the second-moment state is ~2 MB, which is what makes the
    1.5B single-chip training point (BASELINE config 4 family) fit.

    Matches the T5/T5X formulation: decay ``1 - t^-0.8``, update-RMS
    clipping at ``clip_threshold``, optional ``scale_parameter``
    (alpha = max(eps2, RMS(p)) · lr), relative step size
    ``min(1e-2, 1/sqrt(t))`` when no learning_rate is given, and no
    first moment by default (``beta1=None`` — the other 6.2 GB saved).
    """

    def __init__(self, learning_rate=None, beta1: Optional[float] = None,
                 decay_rate: float = 0.8, epsilon1: float = 1e-30,
                 epsilon2: float = 1e-3, clip_threshold: float = 1.0,
                 scale_parameter: bool = True, parameters=None,
                 weight_decay: float = 0.0, grad_clip=None,
                 multi_precision: bool = True):
        self.relative_step = learning_rate is None
        super().__init__(1.0 if learning_rate is None else learning_rate,
                         parameters, weight_decay, grad_clip,
                         multi_precision)
        self.beta1 = beta1
        self.decay_rate = decay_rate
        self.epsilon1, self.epsilon2 = epsilon1, epsilon2
        self.clip_threshold = clip_threshold
        self.scale_parameter = scale_parameter

    @staticmethod
    def _factored(p) -> bool:
        return p.ndim >= 2

    def init_state(self, params):
        s = self._maybe_master_state(params)
        base = s.get("master", params)

        # one fresh zero-size array per leaf: a single shared `empty`
        # buffer would be donated N times by a donated train step
        def vr(p):
            return jnp.zeros(p.shape[:-1] if self._factored(p) else (0,),
                             jnp.float32)

        def vc(p):
            return jnp.zeros(p.shape[:-2] + p.shape[-1:]
                             if self._factored(p) else (0,), jnp.float32)

        def vfull(p):
            return jnp.zeros((0,) if self._factored(p) else p.shape,
                             jnp.float32)

        s["vr"] = _tree_map(vr, base)
        s["vc"] = _tree_map(vc, base)
        s["v"] = _tree_map(vfull, base)
        if self.beta1 is not None:
            s["m"] = _tree_map(
                lambda p: jnp.zeros_like(p, jnp.float32), base)
        s["t"] = jnp.zeros([], jnp.int32)
        return s

    def _update(self, grads, state, params, lr):
        eps1, eps2 = self.epsilon1, self.epsilon2
        t = state["t"] + 1
        tf = t.astype(jnp.float32)
        decay = 1.0 - tf ** (-self.decay_rate)
        # relative step: schedules still compose (lr_fn is identity 1.0
        # unless the user passed a rate)
        step_size = jnp.minimum(1e-2, 1.0 / jnp.sqrt(tf)) \
            if self.relative_step else lr

        def rms(x):
            return jnp.sqrt(jnp.mean(jnp.square(x)) + 1e-30)

        def core(g, vr, vc, v, m, p):
            """One LOGICAL parameter's update → (delta, vr, vc, v, m)."""
            g32 = g.astype(jnp.float32)
            g2 = jnp.square(g32) + eps1
            if self._factored(p):
                vr_n = decay * vr + (1 - decay) * jnp.mean(g2, axis=-1)
                vc_n = decay * vc + (1 - decay) * jnp.mean(g2, axis=-2)
                # v_hat = outer(vr, vc) / mean(vr): rank-1 second moment
                r = vr_n / jnp.mean(vr_n, axis=-1, keepdims=True)
                u = g32 * jax.lax.rsqrt(r)[..., None] * \
                    jax.lax.rsqrt(vc_n)[..., None, :]
                v_n = v
            else:
                v_n = decay * v + (1 - decay) * g2
                u = g32 * jax.lax.rsqrt(v_n)
                vr_n, vc_n = vr, vc
            u = u / jnp.maximum(1.0, rms(u) / self.clip_threshold)
            alpha = step_size * jnp.maximum(eps2, rms(p)) \
                if self.scale_parameter else step_size
            if m is not None:
                m = self.beta1 * m + (1 - self.beta1) * u
                u = m
            delta = (-alpha * u - step_size * self.weight_decay *
                     p.astype(jnp.float32)).astype(p.dtype)
            return delta, vr_n, vc_n, v_n, m

        def leaf(g, vr, vc, v, m, p):
            """ndim>=3 leaves are SCAN-STACKED logical parameters
            ([L, r, c] from scan_layers / pipeline stacking): update
            slices SEQUENTIALLY with lax.map, so the f32 transients
            (g32/u/delta copies) peak at ONE slice, not the whole
            stack — at 1.5B+ single-chip scale the whole-stack
            transients are gigabytes (FEASIBILITY_XL.json) — and the
            update-RMS clip / parameter-scale apply PER SLICE, i.e.
            per logical parameter, matching the unstacked model.

            Gated on big slices (>= 1 Mi elements): a conv kernel
            [O, I, k] is also 3-D but its slices are tiny — hundreds
            of sequential map steps would cost far more than the
            bytes they save."""
            if p.ndim == 3 and p.shape[-2] * p.shape[-1] >= (1 << 20):
                if m is None:
                    def body(xs):
                        d, vrn, vcn, _, _ = core(
                            xs[0], xs[1], xs[2],
                            jnp.zeros((0,), jnp.float32), None, xs[3])
                        return d, vrn, vcn
                    d, vrn, vcn = jax.lax.map(body, (g, vr, vc, p))
                    return d, vrn, vcn, v, None
                def body(xs):
                    d, vrn, vcn, _, mn = core(xs[0], xs[1], xs[2],
                                              jnp.zeros((0,),
                                                        jnp.float32),
                                              xs[3], xs[4])
                    return d, vrn, vcn, mn
                d, vrn, vcn, mn = jax.lax.map(body, (g, vr, vc, m, p))
                return d, vrn, vcn, v, mn
            return core(g, vr, vc, v, m, p)

        is_t = lambda x: isinstance(x, tuple)  # noqa: E731
        if self.beta1 is not None:
            outs = _tree_map(leaf, grads, state["vr"], state["vc"],
                             state["v"], state["m"], params)
        else:
            outs = _tree_map(
                lambda g, vr, vc, v, p: leaf(g, vr, vc, v, None, p),
                grads, state["vr"], state["vc"], state["v"], params)
        updates = _tree_map(lambda o: o[0], outs, is_leaf=is_t)
        new_state = dict(state)
        new_state["vr"] = _tree_map(lambda o: o[1], outs, is_leaf=is_t)
        new_state["vc"] = _tree_map(lambda o: o[2], outs, is_leaf=is_t)
        new_state["v"] = _tree_map(lambda o: o[3], outs, is_leaf=is_t)
        if self.beta1 is not None:
            new_state["m"] = _tree_map(lambda o: o[4], outs,
                                       is_leaf=is_t)
        new_state["t"] = t
        return updates, new_state


class LarsMomentum(Optimizer):
    """LARS (ref: paddle/fluid/operators/optimizers/lars_momentum_op.cu;
    python/paddle/fluid/optimizer.py LarsMomentumOptimizer)."""

    def __init__(self, learning_rate=0.001, momentum: float = 0.9,
                 lars_coeff: float = 0.001, lars_weight_decay: float = 0.0005,
                 parameters=None, grad_clip=None, epsilon: float = 1e-9,
                 multi_precision: bool = True):
        super().__init__(learning_rate, parameters, lars_weight_decay,
                         grad_clip, multi_precision)
        self.momentum = momentum
        self.lars_coeff = lars_coeff
        self.epsilon = epsilon

    def init_state(self, params):
        s = self._maybe_master_state(params)
        base = s.get("master", params)
        s["velocity"] = _tree_map(jnp.zeros_like, base)
        return s

    def _update(self, grads, state, params, lr):
        mu, coeff, wd, eps = (self.momentum, self.lars_coeff,
                              self.weight_decay, self.epsilon)

        def upd(g, v, p):
            g = g.astype(p.dtype)
            p_norm = jnp.sqrt(jnp.sum(jnp.square(p)))
            g_norm = jnp.sqrt(jnp.sum(jnp.square(g)))
            local_lr = jnp.where(
                (p_norm > 0) & (g_norm > 0),
                lr * coeff * p_norm / (g_norm + wd * p_norm + eps), lr)
            v_new = mu * v + local_lr * (g + wd * p)
            return -v_new, v_new
        pairs = _tree_map(upd, grads, state["velocity"], params)
        is_t = lambda x: isinstance(x, tuple)  # noqa: E731
        ns = dict(state)
        ns["velocity"] = _tree_map(lambda pr: pr[1], pairs, is_leaf=is_t)
        return _tree_map(lambda pr: pr[0], pairs, is_leaf=is_t), ns
