"""Layer: the module system.

TPU-native rebuild of the reference's ``nn.Layer``
(reference: python/paddle/fluid/dygraph/layers.py:84 — parameters, buffers,
sublayers, state_dict, hooks, train/eval) with one structural change: JAX
training is functional, so every Layer doubles as a *pytree-of-state
factory*. Eager use reads parameters straight off the object (dygraph
feel); compiled training extracts ``(params, buffers)`` trees and runs the
same ``forward`` under :func:`functional_call`, which temporarily swaps the
traced arrays in and collects mutated buffers (BatchNorm running stats
etc.) afterwards. This replaces the reference's dual dygraph/static worlds
(dygraph VarBase tracer + dy2static AST transpiler,
python/paddle/fluid/dygraph_to_static/program_translator.py) with a single
definition traced by jax.jit.

Parameters carry metadata (trainable, logical sharding axes) in a parallel
dict so the arrays themselves stay plain ``jax.Array`` — no proxy wrapper
in the compute path.
"""

from __future__ import annotations

import contextlib
from collections import OrderedDict
from typing import Any, Callable, Dict, Iterator, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core import dtype as dtype_mod
from . import initializer as I


class Parameter:
    """Declaration-time wrapper marking an array as a trainable parameter.

    Assigning a ``Parameter`` to a Layer attribute registers the underlying
    array in ``layer._parameters``; afterwards attribute access returns the
    bare ``jax.Array``. ``axes`` is the logical sharding annotation consumed
    by ``paddle_tpu.parallel`` (a tuple of logical axis names or None per
    dim, e.g. ``("embed", "mlp")`` for a column-parallel weight).
    """

    def __init__(self, value, trainable: bool = True,
                 axes: Optional[Tuple[Optional[str], ...]] = None):
        self.value = jnp.asarray(value)
        self.trainable = trainable
        self.axes = axes


class ParamMeta:
    __slots__ = ("trainable", "axes")

    def __init__(self, trainable: bool = True, axes=None):
        self.trainable = trainable
        self.axes = axes


def _flatten_name(prefix: str, name: str) -> str:
    return f"{prefix}.{name}" if prefix else name


class Layer:
    """Base class for all neural network modules."""

    def __init__(self):
        object.__setattr__(self, "_parameters", OrderedDict())
        object.__setattr__(self, "_param_meta", {})
        object.__setattr__(self, "_buffers", OrderedDict())
        object.__setattr__(self, "_buffer_persistable", {})
        object.__setattr__(self, "_sublayers", OrderedDict())
        object.__setattr__(self, "training", True)
        object.__setattr__(self, "_forward_pre_hooks", OrderedDict())
        object.__setattr__(self, "_forward_post_hooks", OrderedDict())

    # -- attribute plumbing -------------------------------------------------
    def __setattr__(self, name: str, value: Any) -> None:
        if isinstance(value, Parameter):
            self._parameters[name] = value.value
            self._param_meta[name] = ParamMeta(value.trainable, value.axes)
            self.__dict__.pop(name, None)
        elif isinstance(value, Layer):
            self._sublayers[name] = value
            self.__dict__.pop(name, None)
        elif name in self._parameters:
            self._parameters[name] = jnp.asarray(value)
        elif name in self._buffers:
            self._buffers[name] = value if value is None else jnp.asarray(value)
        else:
            object.__setattr__(self, name, value)

    def __getattr__(self, name: str):
        # Only called when normal lookup fails.
        d = self.__dict__
        for store in ("_parameters", "_buffers", "_sublayers"):
            if store in d and name in d[store]:
                return d[store][name]
        # derived attributes (weight_norm / spectral_norm): recomputed
        # from the live parameters on every access, so no stale value —
        # and no leaked tracer after a jitted functional_call
        derived = d.get("_derived")
        if derived and name in derived:
            return derived[name](self)
        raise AttributeError(
            f"{type(self).__name__!r} object has no attribute {name!r}")

    def __delattr__(self, name: str) -> None:
        for store in (self._parameters, self._buffers, self._sublayers):
            if name in store:
                del store[name]
                self._param_meta.pop(name, None)
                self._buffer_persistable.pop(name, None)
                return
        object.__delattr__(self, name)

    # -- registration API ---------------------------------------------------
    def create_parameter(self, shape, dtype=None,
                         initializer: Optional[Callable] = None,
                         trainable: bool = True, axes=None):
        """Create + return a parameter array (caller assigns it).

        Analog of ``Layer.create_parameter``
        (ref: fluid/dygraph/layers.py create_parameter → LayerHelper).
        """
        dt = dtype_mod.dtype(dtype) if dtype is not None \
            else dtype_mod.get_default_dtype()
        init = initializer or I.get_global_initializer() \
            or I.XavierUniform()
        value = init(shape, dt)
        return Parameter(value, trainable=trainable, axes=axes)

    def add_parameter(self, name: str, param: Parameter) -> None:
        setattr(self, name, param)

    def register_buffer(self, name: str, value, persistable: bool = True):
        """Non-parameter state (running stats, step counters).
        Ref: fluid/dygraph/layers.py register_buffer."""
        self._buffers[name] = None if value is None else jnp.asarray(value)
        self._buffer_persistable[name] = persistable

    def add_sublayer(self, name: str, layer: "Layer") -> "Layer":
        self._sublayers[name] = layer
        return layer

    # -- traversal ----------------------------------------------------------
    def named_sublayers(self, prefix: str = "", include_self: bool = False
                        ) -> Iterator[Tuple[str, "Layer"]]:
        if include_self:
            yield prefix, self
        for name, sub in self._sublayers.items():
            full = _flatten_name(prefix, name)
            yield full, sub
            yield from sub.named_sublayers(full)

    def sublayers(self, include_self: bool = False):
        return [l for _, l in self.named_sublayers(include_self=include_self)]

    def children(self) -> Iterator["Layer"]:
        return iter(self._sublayers.values())

    def named_parameters(self, prefix: str = ""
                         ) -> Iterator[Tuple[str, jax.Array]]:
        for name, p in self._parameters.items():
            yield _flatten_name(prefix, name), p
        for name, sub in self._sublayers.items():
            yield from sub.named_parameters(_flatten_name(prefix, name))

    def parameters(self):
        return [p for _, p in self.named_parameters()]

    def named_trainable_parameters(self, prefix: str = ""
                                   ) -> Iterator[Tuple[str, jax.Array]]:
        meta = self.param_meta(prefix)
        for name, p in self.named_parameters(prefix):
            if meta[name].trainable:
                yield name, p

    def named_buffers(self, prefix: str = "", persistable_only: bool = False
                      ) -> Iterator[Tuple[str, jax.Array]]:
        for name, b in self._buffers.items():
            if b is None:
                continue
            if persistable_only and not self._buffer_persistable.get(name, True):
                continue
            yield _flatten_name(prefix, name), b
        for name, sub in self._sublayers.items():
            yield from sub.named_buffers(_flatten_name(prefix, name),
                                         persistable_only)

    def buffers(self):
        return [b for _, b in self.named_buffers()]

    def param_meta(self, prefix: str = "") -> Dict[str, ParamMeta]:
        out = {}
        for name, m in self._param_meta.items():
            out[_flatten_name(prefix, name)] = m
        for name, sub in self._sublayers.items():
            out.update(sub.param_meta(_flatten_name(prefix, name)))
        return out

    def apply(self, fn: Callable[["Layer"], None]) -> "Layer":
        for sub in self._sublayers.values():
            sub.apply(fn)
        fn(self)
        return self

    # -- train/eval ---------------------------------------------------------
    def train(self) -> "Layer":
        def _set(l):
            object.__setattr__(l, "training", True)
        return self.apply(_set)

    def eval(self) -> "Layer":
        def _set(l):
            object.__setattr__(l, "training", False)
        return self.apply(_set)

    # -- state dict ---------------------------------------------------------
    def state_dict(self, include_buffers: bool = True
                   ) -> "OrderedDict[str, jax.Array]":
        """Flat name→array mapping (ref: layers.py state_dict)."""
        out = OrderedDict(self.named_parameters())
        if include_buffers:
            for name, b in self.named_buffers(persistable_only=True):
                out[name] = b
        return out

    def set_state_dict(self, state: Dict[str, Any],
                       strict: bool = True) -> "Layer":
        missing, unexpected = [], set(state.keys())
        for name, _ in list(self.named_parameters()) + \
                list(self.named_buffers(persistable_only=True)):
            if name in state:
                self._assign_by_path(name, jnp.asarray(state[name]))
                unexpected.discard(name)
            else:
                missing.append(name)
        if strict and (missing or unexpected):
            raise ValueError(
                f"state_dict mismatch: missing={missing}, "
                f"unexpected={sorted(unexpected)}")
        return self

    load_dict = set_state_dict

    def _assign_by_path(self, path: str, value) -> None:
        parts = path.split(".")
        layer = self
        for p in parts[:-1]:
            layer = layer._sublayers[p]
        leaf = parts[-1]
        if leaf in layer._parameters:
            layer._parameters[leaf] = value
        elif leaf in layer._buffers:
            layer._buffers[leaf] = value
        else:
            raise KeyError(f"no parameter/buffer at path {path!r}")

    def _get_by_path(self, path: str):
        parts = path.split(".")
        layer = self
        for p in parts[:-1]:
            layer = layer._sublayers[p]
        leaf = parts[-1]
        if leaf in layer._parameters:
            return layer._parameters[leaf]
        return layer._buffers[leaf]

    # -- dtype / casting ----------------------------------------------------
    def astype(self, dt) -> "Layer":
        dt = dtype_mod.dtype(dt)

        def _cast(l: Layer):
            for k, v in l._parameters.items():
                if jnp.issubdtype(v.dtype, jnp.floating):
                    l._parameters[k] = v.astype(dt)
            for k, v in l._buffers.items():
                if v is not None and jnp.issubdtype(v.dtype, jnp.floating):
                    l._buffers[k] = v.astype(dt)
        return self.apply(_cast)

    to = astype

    # -- hooks --------------------------------------------------------------
    def register_forward_pre_hook(self, hook) -> "HookRemoveHelper":
        handle = HookRemoveHelper(self._forward_pre_hooks)
        self._forward_pre_hooks[handle.id] = hook
        return handle

    def register_forward_post_hook(self, hook) -> "HookRemoveHelper":
        handle = HookRemoveHelper(self._forward_post_hooks)
        self._forward_post_hooks[handle.id] = hook
        return handle

    # -- call ---------------------------------------------------------------
    def forward(self, *args, **kwargs):
        raise NotImplementedError(
            f"{type(self).__name__} must implement forward()")

    # a layer may name the ``jax.named_scope`` its forward runs under:
    # metadata on the ops it emits, which a device trace then carries
    # (profiler/scopes.py). Set on the instance, so that a caller that
    # walks a model's sublayers by hand (the serving engine's programs)
    # emits the same names as the model's own forward.
    _scope: Optional[str] = None

    def __call__(self, *args, **kwargs):
        for hook in self._forward_pre_hooks.values():
            res = hook(self, args)
            if res is not None:
                args = res if isinstance(res, tuple) else (res,)
        if self._scope is None:
            out = self.forward(*args, **kwargs)
        else:
            with jax.named_scope(self._scope):
                out = self.forward(*args, **kwargs)
        for hook in self._forward_post_hooks.values():
            res = hook(self, args, out)
            if res is not None:
                out = res
        return out

    def __repr__(self):
        lines = [type(self).__name__ + "("]
        for name, sub in self._sublayers.items():
            sub_repr = repr(sub).replace("\n", "\n  ")
            lines.append(f"  ({name}): {sub_repr}")
        lines.append(")")
        return "\n".join(lines) if len(lines) > 2 else \
            type(self).__name__ + "()"


class HookRemoveHelper:
    _next_id = 0

    def __init__(self, store: OrderedDict):
        self._store = store
        self.id = HookRemoveHelper._next_id
        HookRemoveHelper._next_id += 1

    def remove(self):
        self._store.pop(self.id, None)


# ---------------------------------------------------------------------------
# Functional bridge: stateful Layer <-> pure function of (params, buffers).
# ---------------------------------------------------------------------------

def split_state(layer: Layer):
    """Extract ``(params, buffers)`` flat dicts (pytrees) from a layer."""
    params = OrderedDict(layer.named_parameters())
    buffers = OrderedDict(layer.named_buffers())
    return params, buffers


@contextlib.contextmanager
def _swapped_state(layer: Layer, params, buffers):
    saved = {}
    for name, v in {**params, **buffers}.items():
        saved[name] = layer._get_by_path(name)
        layer._assign_by_path(name, v)
    try:
        yield
    finally:
        for name, v in saved.items():
            layer._assign_by_path(name, v)


def functional_call(layer: Layer, params, buffers, *args,
                    training: Optional[bool] = None, **kwargs):
    """Run ``layer.forward`` as a pure function.

    Swaps ``params``/``buffers`` into the layer tree, runs forward, reads
    mutated buffers back out, restores the original state, and returns
    ``(output, new_buffers)``. Safe to trace with jax.jit/grad: the swapped
    values may be tracers; the original concrete state is always restored.
    """
    prev_modes = None
    if training is not None:
        prev_modes = [(l, l.training)
                      for l in layer.sublayers(include_self=True)]
        (layer.train() if training else layer.eval())
    try:
        with _swapped_state(layer, params, buffers):
            out = layer(*args, **kwargs)
            new_buffers = OrderedDict(
                (name, layer._get_by_path(name)) for name in buffers)
    finally:
        if prev_modes is not None:
            for l, mode in prev_modes:
                object.__setattr__(l, "training", mode)
    return out, new_buffers


# ---------------------------------------------------------------------------
# Containers (ref: fluid/dygraph/container.py Sequential/LayerList/ParameterList)
# ---------------------------------------------------------------------------

class Sequential(Layer):
    def __init__(self, *layers):
        super().__init__()
        if len(layers) == 1 and isinstance(layers[0], (list, tuple)):
            layers = tuple(layers[0])
        for i, l in enumerate(layers):
            if isinstance(l, tuple):  # (name, layer) pairs
                self.add_sublayer(l[0], l[1])
            else:
                self.add_sublayer(str(i), l)

    def __iter__(self):
        return iter(self._sublayers.values())

    def __len__(self):
        return len(self._sublayers)

    def __getitem__(self, idx):
        if isinstance(idx, slice):
            return Sequential(*list(self._sublayers.values())[idx])
        return list(self._sublayers.values())[idx]

    def forward(self, x):
        for l in self._sublayers.values():
            x = l(x)
        return x


class LayerList(Layer):
    def __init__(self, layers: Sequence[Layer] = ()):
        super().__init__()
        for i, l in enumerate(layers):
            self.add_sublayer(str(i), l)

    def append(self, layer: Layer) -> "LayerList":
        self.add_sublayer(str(len(self._sublayers)), layer)
        return self

    def __iter__(self):
        return iter(self._sublayers.values())

    def __len__(self):
        return len(self._sublayers)

    def __getitem__(self, idx):
        return list(self._sublayers.values())[idx]


class LayerDict(Layer):
    def __init__(self, layers: Optional[Dict[str, Layer]] = None):
        super().__init__()
        if layers:
            for k, v in layers.items():
                self.add_sublayer(k, v)

    def __getitem__(self, key):
        return self._sublayers[key]

    def __setitem__(self, key, layer):
        self.add_sublayer(key, layer)

    def keys(self):
        return self._sublayers.keys()

    def items(self):
        return self._sublayers.items()

    def values(self):
        return self._sublayers.values()
